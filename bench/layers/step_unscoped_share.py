"""Step: of the device's busy time between the first traced fit's start and
the last one's end, the share in operations whose ``op_name`` holds no
``sgd.*`` scope (copies the compiler adds, the ``while`` itself, what the
step does outside its named pieces).  None for a program without scopes."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None or set(reduced["scopes"]) <= {spans.UNSCOPED}:
        return None
    busy = sum(reduced["scopes"].values())
    return 100.0 * reduced["scopes"].get(spans.UNSCOPED, 0.0) / busy
