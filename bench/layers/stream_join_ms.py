"""Stream fold: the DEVICE's time, a micro-batch trained, in the program that
makes a micro-batch's landed row blocks the one array its fit reads
(``gradient_descent._stage_join``: one write a block over the whole row
capacity, the labels' beside the rows'), which the chip runs in front of the
fit: the own time of the operations whose ``op_name`` says the program was
made from the jitted function ``_stage_join`` (``bench/spans.py``'s
``functions``; the chip's compiler keeps the ``sgd.whole`` scope on one write
of 128, so no scope finds them and ``step_unscoped_share`` only counts
them), over the micro-batches of the traced passes.  ``stream_whole_ms`` is
the host's dispatch of the same program.  0 where the passes trained
micro-batches and no such program ran (a stream whose blocks are folded into
totals as they land has nothing to join: the bypass).  None where the run's
trace resolved nothing, no operation names its function (an executable from
before the names), or the fits are no passes of a stream."""

from bench import spans

FUNCTION = "_stage_join"


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None or set(reduced["functions"]) <= {spans.NO_FUNCTION}:
        return None
    batches = spans.micro_batches(reduced)
    if not batches:
        return None
    return reduced["functions"].get(FUNCTION, 0.0) / batches / 1e6
