"""Bytes and operations of one step over BCOO rows with a fixed number of
stored entries a row, from shapes.

``least``: every sampled entry's value and column index once (4 + 4 bytes; a
row's entries fit on the chip, so margin and gradient term need one read; the
row index is implied by the fixed count), the labels, and the weights read
and written.  One multiply-add an entry forward and one backward.

``as_laid_out``: BCOO as the program holds it — value, row and column index
(12 bytes) read forward and again backward, plus a gathered weight and a
scattered term (4 + 4) an entry."""


def dataset_bytes(config: dict, rows: int) -> int:
    return rows * int(config["nnz_per_row"]) * (4 + 8)


def step_work(config: dict, rows: int) -> dict:
    d = int(config["features"])
    batch = max(1, round(float(config["mini_batch_fraction"]) * rows))
    nse = batch * int(config["nnz_per_row"])
    all_nse = rows * int(config["nnz_per_row"])
    return {
        "least": {"bytes": nse * 8 + batch * 4 + 2 * d * 4,
                  "flops": 4 * nse},
        "as_laid_out": {"bytes": all_nse * (2 * 12 + 8) + rows * 4 + 2 * d * 4,
                        "flops": 4 * all_nse},
        "flops_peak": "bf16_flops_per_s",
    }
