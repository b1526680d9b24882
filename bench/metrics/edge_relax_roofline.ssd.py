"""Kernels: the ``edge_relax`` Pallas kernel's share of its roofline in
the SSD sweeps, in %.

Kernel time: every ``tpu_custom_call`` op of the trace whose instruction
is named for the relaxation (``%relax_bucketed.<n>``).  Work: the least
work of the forward and backward sweeps of every batch in the window
(``yardstick.work``), at the chip's published peaks.
"""
from yardstick.peaks import least_seconds
from yardstick.work import plan_least_work


def read(r):
    if r.cell.traffic["mode"] != "ssd":
        return None
    kernel = [row for name, row in r.trace["ops"].items()
              if name.endswith("[tpu_custom_call]") and "relax" in name]
    seconds = sum(row["seconds"] for row in kernel)
    if not seconds:
        return None
    ix = r.index
    work = (plan_least_work(ix.plan_f, r.batch_size)
            + plan_least_work(ix.plan_b, r.batch_size)).scaled(r.batches)
    return 100.0 * least_seconds(work.flops, work.nbytes,
                                 r.device_kind) / seconds
