"""Hierarchical train step — Arena's synchronization scheme on the TPU mesh.

One compiled ``hfl_train_step`` = one cloud round (Eq. 5):

    scan γ2 [ scan γ1 [ per-replica local SGD epoch (scan over minibatches) ]
              edge-aggregate  (all-reduce over the 'fl' axis)   ]
    cloud-aggregate            (all-reduce over 'edge' + 'pod' axes)

Model replicas live as explicit leading (pod, edge, fl) axes on every
parameter leaf, sharded 1:1 onto the replica mesh axes — divergence
between syncs is ordinary per-shard state, and each aggregation lowers to
exactly one all-reduce over exactly the axes whose hierarchy level it
crosses. ICI carries the frequent edge syncs, DCN the rare cloud syncs —
this is the paper's insight transposed to the TPU interconnect hierarchy.

``static`` frequencies compile the loops directly (dry-run / roofline
path); the ``dynamic`` path takes traced per-edge (γ1, γ2) from the Arena
agent with masked upper-bound loops (no recompilation between actions).

Device scopes name a round's phases in the profiler trace, with the
names the Arena round uses (``repro.core.hfl``): ``hfl.local`` (one
local epoch on every replica, with the masking of inactive ones),
``hfl.update`` inside it (the SGD update), ``hfl.edge_agg`` and
``hfl.cloud_agg``. Scopes are metadata: the compiled step is the same.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import mesh as mesh_lib
from repro.models import build_model


@jax.named_scope("hfl.update")
def _sgd(params, grads, lr: float):
    return jax.tree.map(
        lambda p, g: (p.astype(jnp.float32)
                      - lr * g.astype(jnp.float32)).astype(p.dtype),
        params, grads)


def _edge_mean(params):
    """Eq. 1 on the mesh: average replicas over the 'fl' axis (leaf layout
    (pod, edge, fl, ...)). Uniform |D_i| per the input pipeline; the
    size-weighted general form lives in repro.core.hfl."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(
            jnp.mean(a.astype(jnp.float32), axis=2, keepdims=True),
            a.shape).astype(a.dtype), params)


def _cloud_mean(params):
    """Eq. 2 on the mesh: average over ('pod', 'edge', 'fl')."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(
            jnp.mean(a.astype(jnp.float32), axis=(0, 1, 2), keepdims=True),
            a.shape).astype(a.dtype), params)


def _edge_mask(old, new, active_edge):
    """Keep ``old`` wherever the edge has finished its γ2 budget."""
    def m(o, n):
        am = active_edge.reshape((1, -1, 1) + (1,) * (o.ndim - 3))
        return jnp.where(am, n, o)

    return jax.tree.map(m, old, new)


def make_hfl_train_step(cfg, hfl_mesh, *, replicas: Optional[tuple] = None,
                        lr: float = 1e-3,
                        mb_per_epoch: int = 4, remat: bool = True,
                        g1: int = 2, g2: int = 2,
                        dynamic: bool = False, max_g1: int = 4,
                        max_g2: int = 4, attn_chunk: int = 1024,
                        collective_dtype: Optional[str] = None,
                        wkv_chunked: bool = False,
                        seq_shard_acts: bool = False):
    """Returns (train_step, param_shardings, batch_sharding).

    ``replicas`` = (pod, edge, fl) replica counts; each must be a
    multiple of its mesh axis, and replicas beyond the axis size share
    its devices (``mesh_lib.make_hfl_mesh``). Default: one replica per
    replica-axis slot of ``hfl_mesh``.

    static:  train_step(params, batch)            — g1/g2 baked in
    dynamic: train_step(params, batch, g1e, g2e)  — per-edge traced freqs

    ``collective_dtype``: optional cast applied to params before the
    *cloud* aggregation only (beyond-paper optimization: quantized DCN
    sync; see EXPERIMENTS.md §Perf).
    """
    model = build_model(cfg)
    axes = tuple(hfl_mesh.shape[a] for a in mesh_lib.REPLICA_AXES)
    n_pod, n_edge, n_fl = replicas = tuple(replicas or axes)
    if any(r % a for r, a in zip(replicas, axes)):
        raise ValueError(f"replicas {replicas} do not divide over the "
                         f"mesh's replica axes {axes}")
    repl = n_pod * n_edge * n_fl

    act_spec = (NamedSharding(hfl_mesh, P(None, ("fsdp", "tp"), None))
                if seq_shard_acts else None)

    def replica_loss(params, batch):
        return model.loss(params, batch, remat=remat,
                          attn_chunk=attn_chunk, wkv_chunked=wkv_chunked,
                          act_spec=act_spec)

    def epoch_all(params, batch):
        """γ1-inner body: one local epoch on every replica (vmapped over
        the three replica axes)."""
        n_mb = mb_per_epoch

        def one(params, batch):
            def step(p, i):
                b = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a.reshape((n_mb, a.shape[0] // n_mb) + a.shape[1:]),
                        i, 0, keepdims=False), batch)
                g = jax.grad(replica_loss)(p, b)
                return _sgd(p, g, lr), None

            p, _ = jax.lax.scan(step, params, jnp.arange(n_mb))
            return p

        return jax.vmap(jax.vmap(jax.vmap(one)))(params, batch)

    def reshape_batch(batch):
        def r(a):
            b = a.shape[0]
            return a.reshape((n_pod, n_edge, n_fl, b // repl) + a.shape[1:])

        return jax.tree.map(r, batch)

    cast = (lambda t: t) if collective_dtype is None else (
        lambda t: jax.tree.map(
            lambda a: a.astype(collective_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, t))

    @jax.named_scope("hfl.cloud_agg")
    def cloud_agg(params):
        if collective_dtype is None:
            return _cloud_mean(params)
        # quantized DCN sync: cast -> mean over pod/edge -> restore dtype
        lowp = cast(params)
        avg = _cloud_mean(lowp)
        return jax.tree.map(lambda a, ref: a.astype(ref.dtype), avg, params)

    if not dynamic:
        def train_step(params, batch):
            batch = reshape_batch(batch)

            def edge_period(params, _):
                @jax.named_scope("hfl.local")
                def local(params, _):
                    return epoch_all(params, batch), None

                params, _ = jax.lax.scan(local, params, None, length=g1)
                with jax.named_scope("hfl.edge_agg"):
                    return _edge_mean(params), None

            params, _ = jax.lax.scan(edge_period, params, None, length=g2)
            return cloud_agg(params)
    else:
        def train_step(params, batch, g1e, g2e):
            """g1e/g2e: (n_edge,) int32 — the Arena action."""
            batch = reshape_batch(batch)

            def edge_period(carry, t2):
                params = carry
                active2 = t2 < g2e                       # (E,)

                @jax.named_scope("hfl.local")
                def local(params, t1):
                    new = epoch_all(params, batch)
                    act = (t1 < g1e) & active2
                    return _edge_mask(params, new, act), None

                params2, _ = jax.lax.scan(local, params,
                                          jnp.arange(max_g1))
                with jax.named_scope("hfl.edge_agg"):
                    agg = _edge_mean(params2)
                    return _edge_mask(params, agg, active2), None

            params, _ = jax.lax.scan(edge_period, params,
                                     jnp.arange(max_g2))
            return cloud_agg(params)

    # ---- shardings ---------------------------------------------------
    key = jax.random.PRNGKey(0)
    pshape = jax.eval_shape(model.init, key)
    hfl_specs = mesh_lib.hfl_param_specs(cfg, pshape, hfl_mesh)
    param_sh = mesh_lib.shardings(hfl_mesh, hfl_specs)
    batch_spec = P(mesh_lib.REPLICA_AXES)
    batch_sh = NamedSharding(hfl_mesh, batch_spec)
    return train_step, param_sh, batch_sh


def lift_params(params, n_pod: int, n_edge: int, n_fl: int):
    """Broadcast a single model copy into the replicated HFL layout."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_pod, n_edge, n_fl) + a.shape),
        params)


def make_trainer(cfg, replicas: tuple, *, devices=None, seed: int = 0,
                 dynamic: bool = False, **step_kw):
    """The hierarchical train step, ready to run on the devices present.

    Builds the mesh from ``devices`` (default ``jax.devices()``; see
    ``mesh_lib.make_hfl_mesh``), jits ``make_hfl_train_step`` with the
    parameter and batch shardings as in/out shardings and the
    parameters donated, and initializes the lifted parameters from
    ``seed`` directly in their sharded layout. Returns
    ``(step, params, hfl_mesh)``; ``step(params, batch)`` (static) or
    ``step(params, batch, g1e, g2e)`` (dynamic) returns the parameters
    after one cloud round."""
    hfl_mesh = mesh_lib.make_hfl_mesh(replicas, devices)
    step, psh, bsh = make_hfl_train_step(cfg, hfl_mesh, replicas=replicas,
                                         dynamic=dynamic, **step_kw)
    rep = NamedSharding(hfl_mesh, P())
    in_sh = (psh, bsh) + ((rep, rep) if dynamic else ())
    jstep = jax.jit(step, in_shardings=in_sh, out_shardings=psh,
                    donate_argnums=0)
    model = build_model(cfg)
    params = jax.jit(lambda k: lift_params(model.init(k), *replicas),
                     out_shardings=psh)(jax.random.PRNGKey(seed))
    return jstep, params, hfl_mesh


def main():
    """Launcher CLI.

        PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
            --rounds 10 [--reduced] [--dynamic]

    Trains 4 replicas (1 pod x 2 edges x 2 FL devices) on the devices
    present: one chip holds all four, four chips one each, and further
    chips shard each replica's tensors (``mesh_lib.make_hfl_mesh``).
    ``--reduced`` trains the family's toy variant (CPU dev loop).
    --dynamic uses the masked per-edge-frequency step with a Var-Freq-B
    style schedule (the Arena agent plugs in through the same signature).
    """
    import argparse
    import time

    import numpy as np

    from repro.configs import get_config
    from repro.data.synthetic import token_batch
    from repro.launch.cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--g1", type=int, default=2)
    ap.add_argument("--g2", type=int, default=2)
    ap.add_argument("--dynamic", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="the family's reduced toy config")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduce()
    replicas = (1, 2, 2)
    n_edge, repl = replicas[1], int(np.prod(replicas))
    if args.batch % repl:
        args.batch = repl * max(1, args.batch // repl)

    kw = dict(lr=3e-3, mb_per_epoch=max(1, args.batch // repl),
              remat=not args.reduced, attn_chunk=min(1024, args.seq))
    if args.dynamic:
        kw.update(max_g1=args.g1 + 2, max_g2=args.g2 + 2)
    else:
        kw.update(g1=args.g1, g2=args.g2)
    step, params, _ = make_trainer(cfg, replicas, dynamic=args.dynamic,
                                   **kw)
    model = build_model(cfg)
    eval_loss = jax.jit(lambda p, b: model.loss(p, b))
    rng = np.random.default_rng(0)
    for i in range(args.rounds):
        batch = token_batch(i, args.batch, args.seq, cfg.vocab)
        t0 = time.time()
        if args.dynamic:
            # Var-Freq-B style: per-edge freqs (Arena's agent drops in here)
            g1e = jnp.asarray(rng.integers(1, args.g1 + 1, n_edge),
                              jnp.int32)
            g2e = jnp.asarray(rng.integers(1, args.g2 + 1, n_edge),
                              jnp.int32)
            params = step(params, batch, g1e, g2e)
        else:
            params = step(params, batch)
        p0 = jax.tree.map(lambda a: a[0, 0, 0], params)
        l = float(eval_loss(p0, token_batch(9999, args.batch, args.seq,
                                            cfg.vocab)))
        print(f"round {i} loss={l:.4f} dt={time.time()-t0:.1f}s",
              flush=True)


if __name__ == "__main__":
    main()
