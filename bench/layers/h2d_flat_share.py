"""Model harness: the share of a fit's hand-off blocks that crossed in a form
that spares the host's runtime the 2-byte re-tiling for the chip (a C-ordered
array's rows as one flat 1-D run, a Fortran-ordered array's 2-byte items as
32-bit words; the chip then makes its own layout of the block in the write's
program, under ``sgd.stage``): ``flat`` over ``blocks`` of the fits'
``train.h2d`` spans, mean over the traced fits.  1.0 where every block of
every fit went so; 0.0 where the spans carry ``blocks`` and no ``flat`` (a
program that hands over strided row blocks alone: the parent) or the array
took the strided fallback; None where no fit has a ``train.h2d`` span or none
sent a block (a device array)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    shares = []
    for fit in reduced["fits"]:
        h2d = [s["stats"] for s in fit["spans"] if s["name"] == "train.h2d"]
        blocks = sum(int(s.get("blocks", 0)) for s in h2d)
        if blocks:
            shares.append(sum(int(s.get("flat", 0)) for s in h2d) / blocks)
    return sum(shares) / len(shares) if shares else None
