"""Model harness: the first fit of the process at the cell's config with the
compile cache warm — trace, lower, cache read, hand-off and run; what a job
that trains once pays, and what only the program can shorten.  Taken once a
run, inside set-up, by the host's clock.  (The issue's end-to-end
``first_fit_s``: its runs spread by 3.5% of the median, which the contract's
rule for a bound — five times the spread, at most 0.1 — cannot hold.)"""


def read(trace: dict, run: dict):
    first = run.get("first_fit_s")
    return None if first is None else first * 1e3
