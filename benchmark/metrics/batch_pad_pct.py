"""Layer engine: of the member slots the window's stacked dispatches ran
(each the power-of-two bucket of its batch), the share that repeated a
batch's last member, device work done for nobody: 100 x
`batch/pad_slots` delta / `batch/member_slots` delta. A program without
the counters, or a window without a batch, is left out."""


def read(ctx):
    c = ctx["window_counters"]
    if not c.get("batch/member_slots"):
        return None
    return 100.0 * c.get("batch/pad_slots", 0) / c["batch/member_slots"]
