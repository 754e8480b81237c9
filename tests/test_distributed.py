"""Multi-device behaviour, run in subprocesses with XLA host devices forced
BEFORE jax import (the parent test process keeps its single device)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(body: str, devices: int = 8) -> dict:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import json
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("RESULT::" + json.dumps(result))
    """)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=500,
                          env={"PYTHONPATH": str(REPO / "src"),
                               "PATH": "/usr/bin:/bin",
                               "JAX_PLATFORMS": "cpu"},
                          cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("RESULT::"):])


def test_sharded_search_matches_single_index():
    """4-shard shard_map search over 4 devices finds the same neighbors as
    brute force (and the merge returns globally-translated ids)."""
    out = _run("""
        import numpy as np, jax
        from repro.core.distributed import (build_sharded_index,
                                            make_sharded_search, place_on_mesh)
        from repro.core.search.beam import SearchParams
        from repro.data.synthetic import (ground_truth, make_queries,
                                          make_vector_dataset)
        vecs = make_vector_dataset("prop-like", 800, 16, seed=0).astype(np.float32)
        queries = make_queries("prop-like", 16, 16).astype(np.float32)
        gt = ground_truth(vecs, queries, k=5)
        mesh = jax.make_mesh((4,), ("data",))
        index, per = build_sharded_index(vecs, 4, r=16, l_build=32, pq_m=4)
        index = place_on_mesh(index, mesh)
        p = SearchParams(l_size=32, beam_width=4, k=5, rerank_batch=5,
                         r_max=16, universe=per, max_iters=64)
        run = make_sharded_search(mesh, p)
        ids, dists = run(index, queries)
        ids = np.asarray(ids)
        hits = sum(len(set(ids[i].tolist()) & set(gt[i].tolist()))
                   for i in range(len(gt)))
        result = {"recall": hits / gt.size, "max_id": int(ids.max())}
    """, devices=4)
    assert out["recall"] >= 0.85, out
    assert out["max_id"] >= 200        # ids from non-first shards present


def test_compressed_psum_error_feedback():
    """int8 error-feedback psum: one step is quantised (bounded error), the
    residual carries the error so the two-step AVERAGE converges."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.optim.grad_compress import (init_residual,
                                               make_compressed_allreduce)
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        # per-device distinct gradients, leading axis = device axis
        g = {"w": jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))}
        true_mean = np.asarray(g["w"]).mean(0)
        fn = make_compressed_allreduce(mesh, ("data",))
        res = {"w": jnp.zeros((8, 64), jnp.float32)}
        out1, res1 = fn(g, res)
        err1 = float(np.abs(np.asarray(out1["w"])[0] - true_mean).max())
        out2, res2 = fn(g, res1)   # same grads again: residual corrects
        err2 = float(np.abs(((np.asarray(out1["w"])[0] +
                              np.asarray(out2["w"])[0]) / 2) - true_mean).max())
        result = {"err1": err1, "err2": err2,
                  "res_nonzero": bool(np.abs(np.asarray(res1["w"])).max() > 0)}
    """, devices=8)
    assert out["err1"] < 0.1                  # int8 quantisation error bound
    assert out["res_nonzero"]                 # error feedback active
    assert out["err2"] < out["err1"] * 0.75   # feedback improves the average


def test_multidevice_train_step_shards():
    """A 2x4 mesh train step runs with sharded params + batch (data+model)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config, reduce_config
        from repro.models import sharding
        from repro.models.api import Model
        from repro.optim.adamw import AdamWConfig, init_opt_state
        from repro.train.trainer import TrainConfig, make_train_step
        from repro.data.pipeline import TokenPipeline
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = reduce_config(get_config("internlm2-1.8b"), d_model=64)
        model = Model.from_config(cfg)
        with sharding.policy(mesh, None):
            p_sh = model.param_shardings()
            params = model.init(jax.random.PRNGKey(0))
            params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
            opt = init_opt_state(params)
            step = jax.jit(make_train_step(model, AdamWConfig(),
                                           TrainConfig(remat=None,
                                                       attn_mode="dense")))
            pipe = TokenPipeline(vocab=cfg.vocab, global_batch=4, seq_len=32)
            losses = []
            for i in range(3):
                batch = jax.tree_util.tree_map(jnp.asarray, pipe.batch_at(i))
                params, opt, m = step(params, opt, batch)
                losses.append(float(m["loss"]))
        result = {"losses": losses,
                  "sharded": str(jax.tree_util.tree_leaves(params)[1].sharding)}
    """, devices=8)
    assert all(np.isfinite(l) for l in out["losses"])
    assert out["losses"][-1] < out["losses"][0] + 0.5


import numpy as np  # noqa: E402  (used in asserts above)
