"""Model harness: the programs the first fit RESTORED from the store of
exported runners beside the compile cache: the ``build.restore`` spans with
``hit`` 1 under the last root that built something (``bench/first_fit.py``),
cut where the fit ``first_fit_s`` times ended.  1 where ``sgd_run`` was read
back (no trace and no lowering of the program's own code follow: what
``first_fit_trace_ms`` and ``first_fit_lower_ms`` then hold is the restored
call's), 0 where the first fit exported and stored it (a checkout's first
run of a cell) or bypassed the store (the span says why).  None where the
root holds no such span: a program from before the store, or a first fit
that is no ``sgd_run``."""

from bench import first_fit


def read(trace: dict, run: dict):
    root = first_fit.root()
    if root is None:
        return None
    fit_s = run.get("first_fit_s")
    end = root["start"] + (root["dur_s"] if fit_s is None
                           else min(root["dur_s"], fit_s))
    restores = [s for s in root["spans"]
                if s["name"] == "build.restore" and s["start"] < end]
    return sum(s.get("hit") == 1 for s in restores) if restores else None
