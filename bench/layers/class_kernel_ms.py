"""Step: own time per iteration of the class kernel's CALL alone: the device
operations whose ``op_name`` ends in a ``pallas_call`` under the scope
``sgd.class_sums`` (``ops/pallas_kernels.fused_class_sums``' one
``tpu_custom_call``), without the weights' cast and pad in front of it and
the folds behind it, which ``class_sums_ms`` also holds.  Mean over the
traced fits.  Where the kernel IS the step this is ``step_ms`` less what
the step does around the kernel, and its share of its roofline is
``step_roofline``'s.  None where no such call ran (two reads: the parent of
the PR that took the kernel past 128 class rows; a vector of weights; no
device in the trace)."""

from bench import spans
from bench.trace import OPS_LINE, _events, _self_times

SCOPE, CALL = "sgd.class_sums", "pallas_call"


def is_call(op_name: str) -> bool:
    """An ``op_name`` (``jit(sgd_run)/while/body/sgd.class_sums/.../
    jit(_fused_rows_class_sums)/pallas_call:``) that is the kernel's."""
    last = (op_name or "").rstrip(":/").rsplit("/", 1)[-1]
    return last.startswith(CALL) and spans.scope_of(op_name) == SCOPE


def _call_ns(path: str, lo: float, hi: float):
    """Own time of the kernel's calls between ``lo`` and ``hi``, over the
    chips; None where there is none."""
    devices = [p for p in spans.load(path)
               if spans.DEVICE_PLANE.match(p["name"])]
    total, found = 0.0, False
    for plane in devices:
        ops = [e for e in _events(plane, OPS_LINE)
               if e[1] + e[2] > lo and e[1] < hi]
        for name, ns in _self_times(ops).items():
            if is_call(plane["op_names"].get(name)):
                total, found = total + ns / len(devices), True
    return total if found else None


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None or not reduced["fits"]:
        return None
    fits = reduced["fits"]
    ns = _call_ns(spans.find(run), fits[0]["start_ns"], fits[-1]["end_ns"])
    if ns is None:
        return None
    return ns / len(fits) / run["iterations"] / 1e6
