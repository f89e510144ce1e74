"""Multi-device checks run in a subprocess with XLA_FLAGS forcing 8 host
devices (kept out of the main pytest process so everything else sees one
device).  Each check prints 'OK <name>' on success."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.runtime import make_mesh


def check_collectives():
    from repro.parallel.collectives import (
        all_to_all_baseline, binary_exchange_all_to_all, ring_all_gather,
        ring_all_reduce, ring_reduce_scatter)
    mesh = make_mesh((8,), ("model",))
    x = jnp.arange(8 * 16 * 3, dtype=jnp.float32).reshape(8, 16, 3)
    sm = lambda f: jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                             out_specs=P("model"))
    ring = jax.jit(sm(lambda xl: ring_all_reduce(xl, "model", impl="ring")))(x)
    psum = jax.jit(sm(lambda xl: ring_all_reduce(xl, "model", impl="psum")))(x)
    assert np.allclose(np.asarray(ring), np.asarray(psum)), "ring != psum"

    rs = jax.jit(sm(lambda xl: ring_reduce_scatter(xl[0], "model", 0)[None]))(x)
    assert np.allclose(np.asarray(rs), x.sum(0).reshape(8, 2, 3))

    ag = jax.jit(sm(lambda xl: ring_all_gather(xl[0], "model", 0)[None]))(x)
    assert np.allclose(np.asarray(ag)[5], x.reshape(-1, 3))

    y = jax.random.normal(jax.random.PRNGKey(0), (8, 8, 4))
    be = jax.jit(sm(lambda yl: binary_exchange_all_to_all(yl[0], "model")[None]))(y)
    bl = jax.jit(sm(lambda yl: all_to_all_baseline(yl[0], "model")[None]))(y)
    assert np.allclose(np.asarray(be), np.asarray(bl)), "binary exchange"
    print("OK collectives")


def check_sharded_equals_unsharded():
    from repro.configs import get_arch
    from repro.models import forward, init_params, lm_loss
    from repro.parallel.sharding import mesh_axes, parallel_rules
    from repro.parallel.specs import param_pspecs, shardings_for

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = mesh_axes(multi_pod=False)
    for arch in ("deepseek-67b", "mixtral-8x7b", "mamba2-780m"):
        cfg = get_arch(arch).reduced()
        if cfg.n_experts:
            cfg = dataclasses.replace(cfg, capacity_factor=16.0)
        # init identical fp32 params with tp-padding for 4-way TP
        params = init_params(cfg, jax.random.PRNGKey(0), tp=4,
                             dtype=jnp.float32)
        batch = {"tokens": jnp.arange(4 * 32, dtype=jnp.int32
                                      ).reshape(4, 32) % cfg.vocab_size,
                 "labels": jnp.ones((4, 32), jnp.int32)}

        def loss_fn(p, b):
            h = forward(p, cfg, b, remat=False)
            return lm_loss(p, cfg, h, b["labels"])

        plain = float(jax.jit(loss_fn)(params, batch))
        with parallel_rules(rules, mesh):
            pspecs = param_pspecs(params)
            bspecs = {"tokens": P("data", None), "labels": P("data", None)}
            with mesh:
                sharded = float(jax.jit(
                    loss_fn,
                    in_shardings=(shardings_for(mesh, pspecs),
                                  shardings_for(mesh, bspecs)))(params, batch))
        assert abs(plain - sharded) < 3e-2, (arch, plain, sharded)
    print("OK sharded_equals_unsharded")


def check_moe_tp_vs_ep():
    from repro.configs import get_arch
    from repro.models import forward
    from repro.parallel.sharding import mesh_axes, parallel_rules
    from repro.parallel.specs import param_pspecs, shardings_for

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = mesh_axes(multi_pod=False)
    cfg = dataclasses.replace(get_arch("mixtral-8x7b").reduced(),
                              capacity_factor=16.0)
    from repro.models import init_params
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4, dtype=jnp.float32)
    batch = {"tokens": jnp.arange(4 * 16, dtype=jnp.int32
                                  ).reshape(4, 16) % cfg.vocab_size}
    outs = {}
    for impl in ("tp", "ep"):
        for a2a in (("binary", "xla") if impl == "ep" else ("binary",)):
            with parallel_rules(rules, mesh):
                pspecs = param_pspecs(params, moe_impl=impl)
                with mesh:
                    h = jax.jit(lambda p, b: forward(
                        p, cfg, b, moe_ctx={"moe_impl": impl,
                                            "a2a_impl": a2a},
                        remat=False),
                        in_shardings=(shardings_for(mesh, pspecs),
                                      {"tokens": NamedSharding(
                                          mesh, P("data", None))}))(
                        params, batch)
            outs[(impl, a2a)] = np.asarray(h, np.float32)
    base = outs[("tp", "binary")]
    for k, v in outs.items():
        assert np.allclose(base, v, atol=5e-2), (k, np.abs(base - v).max())
    print("OK moe_tp_vs_ep")


def check_ring_allreduce_in_model():
    """ar_impl='ring' (explicit ppermute ring) == psum in the MoE layer."""
    from repro.configs import get_arch
    from repro.models import forward, init_params
    from repro.parallel.sharding import mesh_axes, parallel_rules
    from repro.parallel.specs import param_pspecs, shardings_for

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = mesh_axes(multi_pod=False)
    cfg = dataclasses.replace(get_arch("mixtral-8x7b").reduced(),
                              capacity_factor=16.0)
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4, dtype=jnp.float32)
    batch = {"tokens": jnp.arange(4 * 16, dtype=jnp.int32
                                  ).reshape(4, 16) % cfg.vocab_size}
    outs = []
    for ar in ("psum", "ring"):
        with parallel_rules(rules, mesh):
            pspecs = param_pspecs(params)
            with mesh:
                h = jax.jit(lambda p, b: forward(
                    p, cfg, b, moe_ctx={"ar_impl": ar}, remat=False),
                    in_shardings=(shardings_for(mesh, pspecs),
                                  {"tokens": NamedSharding(
                                      mesh, P("data", None))}))(params, batch)
        outs.append(np.asarray(h, np.float32))
    assert np.allclose(outs[0], outs[1], atol=1e-3)
    print("OK ring_allreduce_in_model")




def check_gpipe():
    """GPipe over a 4-stage 'pod' axis == sequential stage application."""
    from repro.parallel.pipeline import gpipe
    mesh = make_mesh((4,), ("pod",))
    n_micro, mb, dim = 6, 2, 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (4, dim, dim)) * 0.3

    def stage_fn(stage, x):
        w = ws[stage]
        return jnp.tanh(x @ w)

    x_mb = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, dim))

    def run(xr):
        return gpipe(stage_fn, xr, axis="pod", n_micro=n_micro)

    out = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P(),
                            out_specs=P(), check_vma=False))(x_mb)
    # reference: apply the 4 stages sequentially
    ref = x_mb
    for s in range(4):
        ref = jnp.tanh(ref @ ws[s])
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()
    print("OK gpipe")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    checks = {
        "collectives": check_collectives,
        "sharded": check_sharded_equals_unsharded,
        "moe": check_moe_tp_vs_ep,
        "ring": check_ring_allreduce_in_model,
        "gpipe": check_gpipe,
    }
    if which == "all":
        for fn in checks.values():
            fn()
    else:
        checks[which]()
