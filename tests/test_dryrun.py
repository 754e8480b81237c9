"""Dry-run machinery at reduced scale (subprocess with 8 forced devices):
lower+compile train/prefill/decode with production-style shardings, and the
roofline extraction pipeline end to end."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

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
                               "JAX_PLATFORMS": "cpu"}, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("RESULT::"):])


def test_lower_compile_all_phases_small_mesh():
    out = _run("""
        import jax
        from repro.configs import get_config, reduce_config
        from repro.configs.shapes import ShapeSpec
        from repro.launch import roofline
        from repro.launch.dryrun import _rules_for, lower_full
        from repro.models.api import Model
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = reduce_config(get_config("jamba-v0.1-52b"))   # hybrid: hardest
        model = Model.from_config(cfg)
        result = {}
        for spec in (ShapeSpec("t", 64, 8, "train"),
                     ShapeSpec("p", 128, 4, "prefill"),
                     ShapeSpec("d", 128, 8, "decode")):
            rules = _rules_for(cfg, spec, mesh)
            low = lower_full(model, spec, mesh, rules)
            comp = low.compile()
            terms = roofline.analyze(comp)
            result[spec.kind] = {"flops": terms.flops,
                                 "coll": terms.coll_bytes,
                                 "dominant": terms.dominant}
    """)
    for kind in ("train", "prefill", "decode"):
        assert out[kind]["flops"] > 0, out
        assert out[kind]["coll"] > 0, f"{kind}: sharded program must communicate"


def test_collective_parser():
    from repro.launch.roofline import collective_bytes
    hlo = """
      %ag = bf16[16,1024]{1,0} all-gather(bf16[2,1024]{1,0} %x), dims={0}
      %ar = f32[512]{0} all-reduce(f32[512]{0} %y), to_apply=%sum
      %rs = f32[64]{0} reduce-scatter(f32[512]{0} %z), dimensions={0}
      %cp = u8[128]{0} collective-permute(u8[128]{0} %w), pairs={{0,1}}
      %done = f32[4]{0} all-gather-done(f32[4]{0} %h)
    """
    got = collective_bytes(hlo)
    assert got["all-gather"] == 16 * 1024 * 2
    assert got["all-reduce"] == 512 * 4
    assert got["reduce-scatter"] == 512 * 4          # operand bytes cross links
    assert got["collective-permute"] == 128
    assert got["_counts"]["all-gather"] == 1         # -done not double counted


def test_roofline_terms_math():
    from repro.launch.roofline import RooflineTerms, combine
    t = RooflineTerms(flops=197e12, bytes_accessed=819e9, coll_bytes=50e9)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 1.0) < 1e-9
    assert abs(t.collective_s - 1.0) < 1e-9
    assert t.step_time_s == 1.0
    assert abs(t.roofline_fraction(197e12) - 1.0) < 1e-9
    c = combine([(t, 2.0), (t, 1.0)])
    assert abs(c.flops - 3 * 197e12) < 1
