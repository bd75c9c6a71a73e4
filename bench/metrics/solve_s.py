"""Seconds per solve: from the window's start to the end of its last
whole solve, over the solves completed (host clock)."""


def read(run):
    vals = run.rec.get("vals")
    if not vals:
        return None
    return (run.rec["t1"] - run.rec["t0"]) / len(vals)
