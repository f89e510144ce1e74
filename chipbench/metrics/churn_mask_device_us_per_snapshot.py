"""Device time of the churn stream's interval-mask build per interval
built, from the device trace: the build program's executions in the
window (the ``XLA Modules`` events named below, summed over the cell's
chips) over the ``rows`` of the window's ``repro.obs`` spans
``churn.device_masks``.  A program that builds its interval masks on the
host has neither, and the reader returns nothing."""

#: Module of ``repro.sim.jax_backend._interval_fn`` on the device, as the
#: profiler names it: the build of interval masks from occupancy deltas.
MODULE = "jit_build_interval_masks"


def read(r):
    built = sum((s.attrs or {}).get("rows", 0) for s in r.spans
                if s.name == "churn.device_masks")
    device_s = sum(v for k, v in (r.trace or {}).get("modules", {}).items()
                   if k.startswith(MODULE))
    if not built or device_s <= 0:
        return None
    return device_s * 1e6 / built
