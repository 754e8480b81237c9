"""Share of the launched rows still searching per traversal round: the
rows' rounds over the rounds the lockstep loop ran for every row of its
launch, by ``BatchReport`` (%)."""


def read(run):
    c = run.counters
    if not c.get("slot_rounds") or "row_rounds" not in c:
        return None
    return 100.0 * c["row_rounds"] / c["slot_rounds"]
