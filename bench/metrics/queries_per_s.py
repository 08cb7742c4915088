"""Queries completed in the window over the time from the window's start
to the last completion (closed loop, so a stall shows). Host clock."""


def compute(run):
    elapsed = run["window"]["end"] - run["window"]["start"]
    return len(run["records"]) / elapsed if elapsed > 0 else None
