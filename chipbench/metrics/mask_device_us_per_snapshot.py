"""Device time of the sweep's mask draw per snapshot drawn, from the
device trace: the draw program's executions in the window (the ``XLA
Modules`` events named below, summed over the cell's chips) over the
``samples`` of the window's ``repro.obs`` spans ``prng.device_masks``.
A program that draws its masks on the host has neither, and the reader
returns nothing."""

#: Module of ``repro.sim.jax_backend._draw_fn`` on the device, as the
#: profiler names it: the jitted counter-threefry mask draw.
MODULE = "jit_draw_counter_masks"


def read(r):
    drawn = sum((s.attrs or {}).get("samples", 0) for s in r.spans
                if s.name == "prng.device_masks")
    device_s = sum(v for k, v in (r.trace or {}).get("modules", {}).items()
                   if k.startswith(MODULE))
    if not drawn or device_s <= 0:
        return None
    return device_s * 1e6 / drawn
