"""Layer engine: statements a stacked dispatch of the batched lane served
(`query/batch_lane.py`): `batch/coalesced_queries` delta /
`batch/batches` delta over the window. `YDB_TPU_BATCH_MAX` when every
group seals full. A window in which the lane dispatched no batch (a
program whose lane declines the shape, a cell with the lane off) has
nothing to read and is left out."""


def read(ctx):
    c = ctx["window_counters"]
    if not c.get("batch/batches"):
        return None
    return c.get("batch/coalesced_queries", 0) / c["batch/batches"]
