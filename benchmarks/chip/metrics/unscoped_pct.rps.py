"""Share of ``run_pipeline``'s device time in the traced segment that no
stage scope names (``scopes.py``): loop and carry copies, the boundary
counters, anything a stage left out."""
import scopes


def read(run):
    got = scopes.read(run)
    if got is None or scopes.PIPELINE not in got["stages"]:
        return None
    per = got["stages"][scopes.PIPELINE]
    return 100.0 * per.get(scopes.UNSCOPED, 0.0) / sum(per.values())
