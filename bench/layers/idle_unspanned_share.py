"""Device: of the device's idle time inside the traced fits, the share that
no leaf span of the program covers (a span with spans inside it covers only
through them).  Says whether the spans are complete: what it leaves is host
work nobody has named.  None for a program that has no span at all."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None or not any(f["spans"] for f in reduced["fits"]):
        return None
    idle = sum(sum(f["idle"].values()) for f in reduced["fits"])
    bare = sum(f["idle"].get(spans.UNSPANNED, 0.0) for f in reduced["fits"])
    return 100.0 * bare / idle if idle else None
