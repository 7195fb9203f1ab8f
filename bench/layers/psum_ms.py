"""Step: own time of the device operations traced under ``sgd.allreduce``
(``make_step``'s ``psum`` of the gradient, loss and count sums across the
data mesh) per iteration, mean over the chips and the traced fits.  None where
the fit runs on one device and no operation carries the scope."""

from bench import spans


def read(trace: dict, run: dict):
    return spans.scope_ms(trace, run, "sgd.allreduce")
