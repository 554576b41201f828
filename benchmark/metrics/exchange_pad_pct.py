"""Layer mesh: the share of the bytes the window's exchanges put on the
wire that was padding: 100 x (1 - `mesh/exchange_live_bytes/*` delta /
`mesh/exchange_bytes/*` delta), every kind summed. The program counts both
on the host from shapes. A program without the counters is left out."""


def read(ctx):
    c = ctx["window_counters"]
    wire = sum(v for k, v in c.items()
               if k.startswith("mesh/exchange_bytes/"))
    live = sum(v for k, v in c.items()
               if k.startswith("mesh/exchange_live_bytes/"))
    return 100.0 * (1.0 - live / wire) if wire else None
