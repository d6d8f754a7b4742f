"""Complexes per second: complexes completed in the window, over the
window's seconds, counted by the refinement step. Each step the window
refined counts 1/steps of its complex, so a window that cuts a complex
counts its share and the rate is not quantised to whole complexes (four
slots end their complexes together). A complex that ended in failure
counts nothing, and no complex counts more than its steps (a retried one
is not counted twice)."""
from bench.common import FAILED


def read(ctx):
    w = ctx.window
    per = ctx.mix["sizes"]["steps"]
    bad = {r.rid for r in w.records if r.status in FAILED}
    steps = {}
    for rid in w.work("stepped_rid"):
        if rid not in bad:
            steps[rid] = steps.get(rid, 0) + 1
    done = sum(min(n, per) for n in steps.values())
    ended = sum(1 for r in w.records
                if r.status == "OK" and w.inside(r.finished))
    ctx.note(f"complexes: {done} refinement steps of complexes that did not "
             f"fail, {ended} complexes ended OK in the window, {len(bad)} "
             f"failed")
    return done / per / w.seconds if w.seconds > 0 else None
