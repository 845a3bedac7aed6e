"""Pipeline (stage) parallelism over the ``pipe`` mesh axis — GPipe schedule.

Net-new capability (the classical-Spark reference has no model parallelism at
all — SURVEY §2.7); completes the mesh-axis family so every parallelism
(dp/fsdp/tp/sp/ep/pp) is an axis of ONE ``jax.sharding.Mesh``.

Design (the standard SPMD pipelining recipe on TPU):

* Stage s's parameters live only on pipe-coordinate s: the stacked param
  pytree has a leading ``[n_stages, ...]`` axis sharded over ``pipe``, so
  per-device memory is one stage's weights.
* The microbatch stream flows through a rotating buffer: at schedule tick t,
  stage 0 ingests microbatch t (while t < n_micro), every stage applies its
  layer to whatever it holds, and activations ``ppermute`` one hop down the
  ring (ICI neighbor exchange — the same collective ring attention uses).
* After ``n_stages - 1 + n_micro`` ticks every microbatch has crossed all
  stages; outputs are collected on the LAST stage and psum-broadcast back
  (tiny tensors in the estimator use cases; callers that want them sharded
  can keep the last-stage copy).
* The whole schedule is a ``lax.scan`` over ticks — compile size independent
  of both ring length and microbatch count, and differentiable by autodiff
  (ppermute's transpose is the reverse permute; the scan transposes to the
  reverse-time scan — 1F1B-style memory comes from ``jax.checkpoint`` on the
  stage fn if needed).

The bubble fraction is the textbook (S-1)/(S-1+M): callers pick
``n_micro >> n_stages`` to amortize.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


__all__ = ["pipeline_apply", "pipeline_apply_interleaved",
           "pipeline_apply_scattered", "pipeline_sharded",
           "stack_stage_params"]


def _to_varying(x, axis_name):
    """Type ``x`` as varying over ``axis_name`` (shard_map's vma typing).
    ``pcast`` rejects varying -> varying, so a value that already varies —
    ``zeros_like`` of a pipe-sharded input — passes through."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


def stack_stage_params(stage_params_list):
    """[params_stage0, ...] -> one pytree with a leading stage axis (shard it
    over ``pipe``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params_list)


def _stage_preamble(stage_fn, stacked_params, axis_name, remat):
    """Shared per-device setup for both schedules: optional remat wrap, axis
    geometry, and the one-stage-per-device check. Returns
    ``(stage_fn, n_stages, idx, my_params)``."""
    if remat:
        # recompute stage activations in the backward scan instead of saving
        # every tick's outputs — the GPipe memory trade
        stage_fn = jax.checkpoint(stage_fn)
    n_stages = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    shard = jax.tree.leaves(stacked_params)[0].shape[0]
    if shard != 1:
        raise ValueError(
            f"pipeline: stage count must equal the {axis_name!r} axis size "
            f"({n_stages}); this device holds {shard} stages — only the "
            f"first would run (wrong results, not an error, if allowed)")
    my_params = jax.tree.map(lambda p: p[0], stacked_params)
    return stage_fn, n_stages, idx, my_params


def pipeline_apply(stage_fn, stacked_params, x_micro, axis_name: str = "pipe",
                   remat: bool = False):
    """Run ``n_micro`` microbatches through ``n_stages`` chained stages.

    Call INSIDE ``shard_map`` (or via :func:`pipeline_sharded`). Per-device
    arguments:

      stage_fn:       ``(params, x) -> y`` — one stage's computation; y must
                      have x's pytree structure/shapes/dtypes (chainable
                      stages). ``x`` may be ANY pytree — e.g. ``(h, mask)``
                      so attention masks travel with their microbatch (a
                      stage returns the mask unchanged).
      stacked_params: THIS device's stage params (leading stage axis already
                      consumed by sharding: ``[1, ...]`` per leaf).
      x_micro:        pytree of ``[n_micro, mb, ...]`` microbatches (stage 0
                      reads them; other devices pass zeros of the same
                      shapes).

    Returns the same pytree of ``[n_micro, mb, ...]`` outputs, valid on
    every device (psum off the last stage).
    """
    stage_fn, n_stages, idx, my_params = _stage_preamble(
        stage_fn, stacked_params, axis_name, remat)
    n_micro = jax.tree.leaves(x_micro)[0].shape[0]
    n_ticks = n_stages - 1 + n_micro
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    tmap = jax.tree.map

    def tick(carry, t):
        state, outs = carry
        # stage 0 ingests microbatch t; everyone else keeps the rotated state
        feed = tmap(lambda xm, st: jnp.where(
            t < n_micro, xm[jnp.minimum(t, n_micro - 1)], jnp.zeros_like(st)),
            x_micro, state)
        inp = tmap(lambda fd, st: jnp.where(idx == 0, fd, st), feed, state)
        y = stage_fn(my_params, inp)
        # the LAST stage finished microbatch t - (n_stages - 1) at this tick
        m = t - (n_stages - 1)
        take = (idx == n_stages - 1) & (m >= 0)
        outs = tmap(lambda os, yy: jax.lax.dynamic_update_index_in_dim(
            os, jnp.where(take, yy, os[jnp.maximum(m, 0)]),
            jnp.maximum(m, 0), axis=0), outs, y)
        state = tmap(lambda yy: jax.lax.ppermute(yy, axis_name, perm), y)
        return (state, outs), None

    # the carry becomes pipe-VARYING inside the loop (ppermute/idx-dependent
    # writes); the init must carry the same varying-axes type or scan rejects
    # the carry under shard_map's vma checking
    state0 = tmap(lambda xm: _to_varying(jnp.zeros_like(xm[0]), axis_name), x_micro)
    outs0 = tmap(lambda xm: _to_varying(jnp.zeros_like(xm), axis_name), x_micro)
    (_, outs), _ = jax.lax.scan(tick, (state0, outs0),
                                jnp.arange(n_ticks, dtype=jnp.int32))
    # only the last stage holds real outputs; zero elsewhere -> psum = bcast
    outs = tmap(lambda os: jnp.where(idx == n_stages - 1, os,
                                     jnp.zeros_like(os)), outs)
    return tmap(lambda os: jax.lax.psum(os, axis_name), outs)


def pipeline_apply_scattered(stage_fn, stacked_params, x_local,
                             axis_name: str = "pipe", remat: bool = False):
    """Memory-scaled variant of :func:`pipeline_apply`: microbatch inputs AND
    outputs are sharded over the pipe axis (device d owns microbatches
    ``[d*chunk, (d+1)*chunk)``), so per-device live memory is
    ``O(n_micro / n_stages)`` owned microbatches plus three in-flight slots —
    never the replicated ``O(n_micro)`` buffers of the GPipe entry point.

    Mechanics (all static-shape, all ICI neighbor traffic):

    * FEED ring (reverse rotation): slot d holds microbatch ``t + d`` at tick
      t; a device swaps in its own copy whenever that index falls in its
      chunk, and stage 0 consumes slot 0 — microbatch t arrives exactly on
      schedule without ever being replicated.
    * compute + forward rotation: identical to :func:`pipeline_apply`.
    * DRAIN ring (forward rotation): a finished microbatch enters at the last
      stage and rides the ring; every device sees it within S-1 hops and its
      owner copies it into the local output chunk (idempotent on later
      passes, so stale entries are harmless).

    Tick count grows from ``S-1+M`` to ``M + 2S - 2`` (the drain tail).
    """
    stage_fn, n_stages, idx, my_params = _stage_preamble(
        stage_fn, stacked_params, axis_name, remat)
    chunk = jax.tree.leaves(x_local)[0].shape[0]
    n_micro = chunk * n_stages
    n_ticks = n_micro + 2 * n_stages - 2
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    rev = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    tmap = jax.tree.map

    def tick(carry, t):
        state, feed, drain, drain_m, outs = carry
        # feed ring: this device's slot carries microbatch t + idx
        m_here = t + idx
        local_i = jnp.clip(m_here - idx * chunk, 0, chunk - 1)
        mine = (m_here >= idx * chunk) & (m_here < (idx + 1) * chunk)
        feed = tmap(lambda xl, f: jnp.where(mine, xl[local_i], f),
                    x_local, feed)
        inp = tmap(lambda f, st: jnp.where(idx == 0, f, st), feed, state)
        y = stage_fn(my_params, inp)
        # drain ring: the last stage finished microbatch t - (S-1) this tick
        m_done = t - (n_stages - 1)
        fresh = (idx == n_stages - 1) & (m_done >= 0) & (m_done < n_micro)
        drain = tmap(lambda yy, dr: jnp.where(fresh, yy, dr), y, drain)
        drain_m = jnp.where(fresh, m_done, drain_m)
        # owners copy passing microbatches into their local output chunk
        own = (drain_m >= idx * chunk) & (drain_m < (idx + 1) * chunk)
        slot = jnp.clip(drain_m - idx * chunk, 0, chunk - 1)
        outs = tmap(lambda os, dr: jax.lax.dynamic_update_index_in_dim(
            os, jnp.where(own, dr, os[slot]), slot, axis=0), outs, drain)
        state = tmap(lambda yy: jax.lax.ppermute(yy, axis_name, fwd), y)
        feed = tmap(lambda f: jax.lax.ppermute(f, axis_name, rev), feed)
        drain = tmap(lambda d: jax.lax.ppermute(d, axis_name, fwd), drain)
        drain_m = jax.lax.ppermute(drain_m, axis_name, fwd)
        return (state, feed, drain, drain_m, outs), None

    one = tmap(lambda xl: _to_varying(jnp.zeros_like(xl[0]), axis_name), x_local)
    outs0 = tmap(lambda xl: _to_varying(jnp.zeros_like(xl), axis_name), x_local)
    m0 = _to_varying(jnp.int32(-1), axis_name)
    (_, _, _, _, outs), _ = jax.lax.scan(
        tick, (one, tmap(jnp.copy, one), tmap(jnp.copy, one), m0, outs0),
        jnp.arange(n_ticks, dtype=jnp.int32))
    return outs


def pipeline_apply_interleaved(stage_fn, stacked_params, x_micro,
                               axis_name: str = "pipe", remat: bool = False):
    """Interleaved (circular) schedule: device d holds ``v`` ROUND-ROBIN
    stage chunks (global stage ``d + c*S`` at local chunk c), so a payload
    hops to the next device every tick and wraps from the last device back
    to device 0 into its next chunk. With L = S*v total stages the bubble
    shrinks from GPipe's ``(S-1)/(S-1+M)`` (stages fused v-per-device) to
    ``~S/(M*v + S)`` — the Megatron interleaved-schedule effect, here as
    one ``lax.scan`` over a single rotating slot per device.

    Per-device arguments: ``stacked_params`` leading axis = v chunks in
    round-robin order (``pipeline_sharded`` does the permutation);
    ``x_micro`` replicated ``[M, mb, ...]`` with M divisible by S. Outputs
    are captured on device 0 (where completed payloads wrap to) and
    psum-broadcast, like :func:`pipeline_apply`.
    """
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    n_stages = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    v = jax.tree.leaves(stacked_params)[0].shape[0]
    n_micro = jax.tree.leaves(x_micro)[0].shape[0]
    if n_micro % n_stages:
        raise ValueError(
            f"interleaved schedule needs n_micro ({n_micro}) divisible by "
            f"the {axis_name!r} axis size ({n_stages})")
    S, round_len = n_stages, n_stages * v
    n_ticks = n_micro * v + S
    fwd = [(i, (i + 1) % S) for i in range(S)]
    tmap = jax.tree.map

    def tick(carry, t):
        state, outs = carry
        in_round = (t % round_len) < S  # injection/arrival window
        # a payload arriving at device 0 in the window is COMPLETE: it was
        # chunk v-1 on device S-1 last tick. Its identity follows from the
        # deterministic schedule alone.
        m_done = (t // round_len) * S + t % round_len - S
        take = (idx == 0) & in_round & (m_done >= 0) & (m_done < n_micro)
        slot = jnp.clip(m_done, 0, n_micro - 1)
        outs = tmap(lambda os, st: jax.lax.dynamic_update_index_in_dim(
            os, jnp.where(take, st, os[slot]), slot, axis=0), outs, state)
        # device 0 injects a fresh microbatch in the same window
        m_in = (t // round_len) * S + t % round_len
        inject = (idx == 0) & in_round & (m_in < n_micro)
        inp = tmap(lambda xm, st: jnp.where(
            inject, xm[jnp.clip(m_in, 0, n_micro - 1)], st), x_micro, state)
        # local chunk this tick: ((t - d) // S) mod v
        c = jnp.mod(jnp.floor_divide(t - idx, S), v)
        params_c = tmap(lambda p: jax.lax.dynamic_index_in_dim(
            p, c, axis=0, keepdims=False), stacked_params)
        y = stage_fn(params_c, inp)
        state = tmap(lambda yy: jax.lax.ppermute(yy, axis_name, fwd), y)
        return (state, outs), None

    state0 = tmap(lambda xm: _to_varying(jnp.zeros_like(xm[0]), axis_name), x_micro)
    outs0 = tmap(lambda xm: _to_varying(jnp.zeros_like(xm), axis_name), x_micro)
    (_, outs), _ = jax.lax.scan(tick, (state0, outs0),
                                jnp.arange(n_ticks, dtype=jnp.int32))
    outs = tmap(lambda os: jnp.where(idx == 0, os, jnp.zeros_like(os)), outs)
    return tmap(lambda os: jax.lax.psum(os, axis_name), outs)


def pipeline_sharded(mesh_ctx, stage_fn, stacked_params, x_micro,
                     axis_name: str = "pipe", remat: bool = False,
                     io: str = "replicated", interleave: int = 1):
    """Full-array entry point: shard_map the pipeline schedule over the
    mesh's ``pipe`` axis (params stage-sharded). Falls back to a sequential
    stage chain when the axis is absent/size-1.

    ``io`` picks the microbatch layout:

    * ``"replicated"`` (GPipe default): microbatches replicated in, outputs
      psum-broadcast to every device — right for the estimator-sized
      tensors this library pipelines by default.
    * ``"sharded"``: microbatches and outputs sharded over the pipe axis
      (``n_micro`` must divide by it) via :func:`pipeline_apply_scattered` —
      per-device activation memory scales as 1/n_stages, the production
      layout for real model sizes.

    ``interleave=v`` (with ``n_stages == pipe_size * v``) runs the circular
    schedule instead: stages assigned round-robin (device d gets stages
    ``d, d+S, ...``), cutting the pipeline bubble by ~v at the cost of a
    param-chunk select per tick. Requires ``io='replicated'`` and
    ``n_micro`` divisible by the axis size.
    """
    from jax.sharding import PartitionSpec as P

    if io not in ("replicated", "sharded"):
        raise ValueError(f"io must be 'replicated' or 'sharded', got {io!r}")
    if interleave < 1:
        raise ValueError(f"interleave must be >= 1, got {interleave}")
    if interleave > 1 and io != "replicated":
        raise ValueError("interleave > 1 requires io='replicated'")
    mesh = getattr(mesh_ctx, "mesh", mesh_ctx)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    pipe_size = sizes.get(axis_name, 1)
    if pipe_size > 1 and n_stages != pipe_size * interleave:
        raise ValueError(
            f"pipeline_sharded: {n_stages} stages cannot shard over a "
            f"{axis_name!r} axis of size {pipe_size}"
            + (f" with interleave={interleave} (need pipe*interleave "
               "stages)" if interleave > 1 else " (one stage per device)"))
    # validated BEFORE the size-1 fallback so misuse surfaces in
    # single-device dev runs, not first on the deployment mesh
    if interleave > 1:
        if n_stages % interleave:
            raise ValueError(
                f"pipeline_sharded: {n_stages} stages cannot interleave by "
                f"{interleave} (need pipe*interleave stages)")
        n_micro = jax.tree.leaves(x_micro)[0].shape[0]
        ring = pipe_size if pipe_size > 1 else n_stages // interleave
        if n_micro % ring:
            raise ValueError(
                f"interleaved schedule needs n_micro ({n_micro}) divisible "
                f"by the {axis_name!r} axis size ({ring})")
    if io == "sharded":
        n_micro = jax.tree.leaves(x_micro)[0].shape[0]
        if n_micro % max(pipe_size, n_stages):
            raise ValueError(
                f"io='sharded' needs n_micro ({n_micro}) divisible by the "
                f"{axis_name!r} axis size ({max(pipe_size, n_stages)})")
    if pipe_size <= 1:
        def seq_apply(params_all, xs):
            n_st = jax.tree.leaves(params_all)[0].shape[0]
            y = xs
            for s in range(n_st):
                y = jax.vmap(lambda x: stage_fn(
                    jax.tree.map(lambda p: p[s], params_all), x))(y)
            return y
        return seq_apply(stacked_params, x_micro)

    if interleave > 1:
        # shard_map splits the leading axis contiguously, so permute the
        # stack: position d*v + c must hold global stage d + c*S
        S, vv = pipe_size, interleave
        perm = [(i // vv) + (i % vv) * S for i in range(n_stages)]
        stacked_params = jax.tree.map(
            lambda p: jnp.take(p, jnp.asarray(perm), axis=0), stacked_params)
        fn = functools.partial(pipeline_apply_interleaved, stage_fn,
                               axis_name=axis_name, remat=remat)
        micro_spec = jax.tree.map(lambda _: P(), x_micro)
    elif io == "sharded":
        fn = functools.partial(pipeline_apply_scattered, stage_fn,
                               axis_name=axis_name, remat=remat)
        micro_spec = jax.tree.map(lambda _: P(axis_name), x_micro)
    else:
        fn = functools.partial(pipeline_apply, stage_fn, axis_name=axis_name,
                               remat=remat)
        micro_spec = jax.tree.map(lambda _: P(), x_micro)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stacked_params),
                  micro_spec),
        out_specs=micro_spec)
    return mapped(stacked_params, x_micro)
