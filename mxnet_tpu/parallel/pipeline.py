"""Pipeline parallelism over a mesh 'pipe' axis.

New capability vs the reference (SURVEY.md §2.5: its only model
parallelism was ctx-group graph surgery with _CrossDeviceCopy inserts,
graph_executor.cc:242-318, example/model-parallel-lstm). TPU-native
design: every stage's weights live on its own mesh slice; microbatches
stream through the ring with `lax.ppermute` activations transfers (ICI
neighbor hops) under `shard_map` — the standard GPipe-style schedule
expressed as a collective program, compiled once by XLA.

The schedule: with S stages and M microbatches, run S+M-1 ticks; at
tick t, stage s processes microbatch t-s (bubble at the ends). Each
device holds ONE stage; the activation buffer rotates by one stage per
tick.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _pcast_varying(x, axis_name):
    return jax.lax.pcast(x, (axis_name,), to="varying")


def _stage_apply(fn, params, x, stage_idx):
    """Apply the per-stage fn with this device's stage params."""
    return fn(params, x, stage_idx)


def pipeline_apply(fn, stage_params, microbatches, mesh,
                   axis_name="pipe"):
    """Run a pipeline of S stages over M microbatches.

    fn(params_for_stage, x, stage_index) -> y   (same shape as x)
    stage_params: pytree whose leaves have leading dim S (stage-major;
      sharded over `axis_name`).
    microbatches: (M, ...) array of microbatch inputs (replicated).
    Returns (M, ...) outputs after the last stage.
    """
    s = mesh.shape[axis_name]
    m = microbatches.shape[0]

    def shard_fn(params, mb):
        # params leaves: (1, ...) local stage slice; mb: (M, ...) full
        idx = jax.lax.axis_index(axis_name)
        local = jax.tree_util.tree_map(lambda p: p[0], params)
        ticks = s + m - 1
        x_shape = mb.shape[1:]
        buf = jnp.zeros(x_shape, mb.dtype)  # activation held here
        buf = _pcast_varying(buf, axis_name)
        outs = jnp.zeros((m,) + x_shape, mb.dtype)
        outs = _pcast_varying(outs, axis_name)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t; other stages use the
            # activation that just arrived from the left neighbor
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(
                idx == 0,
                mb[mb_idx],
                buf,
            )
            active = (t - idx >= 0) & (t - idx < m)
            y = _stage_apply(fn, local, x_in, idx)
            y = jnp.where(active, y, buf)
            # last stage writes its finished microbatch t-(S-1)
            done_idx = jnp.clip(t - (s - 1), 0, m - 1)
            write = (idx == s - 1) & (t >= s - 1)
            outs = jnp.where(
                write,
                outs.at[done_idx].set(y),
                outs,
            )
            # rotate activations one stage to the right
            perm = [(i, (i + 1) % s) for i in range(s)]
            buf_next = jax.lax.ppermute(y, axis_name, perm)
            return buf_next, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)),
            axis_name,
        )
        return outs

    spec_params = jax.tree_util.tree_map(
        lambda _: P(axis_name), stage_params
    )
    fn_sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
    )
    return fn_sharded(stage_params, microbatches)


def pipeline_apply_hetero(stage_fns, flat_params, flat_auxs,
                          microbatches, mesh, axis_name="pipe"):
    """GPipe over HETEROGENEOUS stages — arbitrary per-stage programs,
    shape changes at boundaries, aux (BatchNorm) state — still ONE
    compiled SPMD program with per-stage memory scaling.

    The reference could split an arbitrary graph across devices with
    ctx groups (example/model-parallel-lstm/lstm.py:48-99); a
    homogeneous stage stack can't express embedding + blocks + head.
    SPMD needs every device to run the same program, so heterogeneity
    is encoded as data, not code:

      - each stage's parameters are flattened into one padded fp
        vector; the stack (S, Lmax) shards over `axis_name`, so a
        device holds ONLY its stage's weights (memory scales with S);
      - the stage body is `lax.switch(axis_index)` over the S stage
        functions — one program, S branches, each statically shaped;
      - boundary activations ride the ppermute ring as flat padded
        vectors of size max-over-boundaries; each branch unflattens
        its true input shape and re-pads its output.

    stage_fns: list of S callables
        fn_s(flat_param_vec, flat_aux_vec, xs, mb_idx)
          -> (ys, new_flat_aux_vec)
        where xs is a TUPLE of stage s's true-shaped inputs (for s=0 a
        1-tuple taken directly from `microbatches`, so integer token
        inputs are fine) and ys is a tuple of its true-shaped outputs —
        stage s+1's i-th input receives stage s's i-th output
        (residual/carry boundaries ride the same ring payload).
        Shapes are declared by `stage_fns[s].in_shapes` /
        `.in_dtypes` / `.out_shapes` / `.out_dtypes` attributes
        (lists, set by the caller). The LAST stage must declare exactly
        one output (the pipeline's result).
    flat_params: (S, Lmax) stage-major padded parameter stack.
    flat_auxs:   (S, Amax) stage-major padded aux stack (Amax may be 0).
    microbatches: (M, ...) stage-0 inputs, replicated.
    Returns ((M, *out_shape_last) outputs, (S, Amax) updated auxs).
    """
    s = mesh.shape[axis_name]
    m = microbatches.shape[0]
    assert len(stage_fns) == s
    assert len(stage_fns[-1].out_shapes) == 1, \
        "last pipeline stage must have exactly one output"

    import numpy as np

    def _payload(f):
        return sum(int(np.prod(sh)) for sh in f.out_shapes)

    last_shape = tuple(stage_fns[-1].out_shapes[0])
    out_dtype = stage_fns[-1].out_dtypes[0]
    # ring payload: the largest flattened boundary activation SET
    # (all of a stage's outputs concatenated). The LAST stage's output
    # never rides the ring (stage 0 ignores its incoming buf), so it
    # is excluded — for an LM whose head emits vocab-sized logits this
    # keeps the ppermute at d_model width.
    emax = max((_payload(f) for f in stage_fns[:-1]), default=1)

    def shard_fn(params, auxs, mb):
        idx = jax.lax.axis_index(axis_name)
        p_local = params[0]  # (Lmax,) this stage's padded weights
        a_local = auxs[0]    # (Amax,)
        ticks = s + m - 1
        buf = jnp.zeros((emax,), jnp.float32)
        buf = _pcast_varying(buf, axis_name)
        outs = jnp.zeros((m,) + last_shape, out_dtype)
        outs = _pcast_varying(outs, axis_name)
        a_var = a_local  # sharded input: already axis-varying

        def make_branch(si):
            fn = stage_fns[si]

            def branch(buf, a, mb_idx):
                if si == 0:
                    xs = (mb[mb_idx],)
                else:
                    xs, off = [], 0
                    for sh, dt in zip(fn.in_shapes, fn.in_dtypes):
                        e = int(np.prod(sh))
                        xs.append(
                            buf[off:off + e].reshape(sh).astype(dt))
                        off += e
                    xs = tuple(xs)
                ys, a2 = fn(p_local, a, xs, mb_idx)
                flat = jnp.concatenate(
                    [jnp.ravel(y).astype(jnp.float32) for y in ys])
                if flat.shape[0] > emax:  # last stage: ring discards it
                    flat = flat[:emax]
                pad = emax - flat.shape[0]
                if pad:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros((pad,), jnp.float32)])
                return flat, a2, ys[0] if si == s - 1 else None

            return branch

        branches = [make_branch(si) for si in range(s)]

        def run_stage(buf, a, mb_idx):
            # last-stage output must be a uniform shape across
            # branches for lax.switch: non-last branches fabricate a
            # zero one
            def wrap(b):
                def f(args):
                    buf, a, mb_idx = args
                    flat, a2, y = b(buf, a, mb_idx)
                    if y is None:
                        y = _pcast_varying(
                            jnp.zeros(last_shape, out_dtype),
                            axis_name)
                    return flat, a2, y
                return f

            return jax.lax.switch(
                idx, [wrap(b) for b in branches], (buf, a, mb_idx))

        def tick(t, carry):
            buf, outs, a = carry
            mb_idx = jnp.clip(t - idx, 0, m - 1)
            active = (t - idx >= 0) & (t - idx < m)
            y_flat, a2, y_last = run_stage(buf, a, mb_idx)
            y_flat = jnp.where(active, y_flat, buf)
            a = jnp.where(active, a2, a)
            done_idx = jnp.clip(t - (s - 1), 0, m - 1)
            write = (idx == s - 1) & (t >= s - 1)
            outs = jnp.where(
                write, outs.at[done_idx].set(y_last), outs)
            perm = [(i, (i + 1) % s) for i in range(s)]
            buf_next = jax.lax.ppermute(y_flat, axis_name, perm)
            return buf_next, outs, a

        buf, outs, a_var = jax.lax.fori_loop(
            0, ticks, tick, (buf, outs, a_var))
        outs = jax.lax.psum(
            jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)),
            axis_name,
        )
        return outs, a_var[None]

    fn_sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P()),
        out_specs=(P(), P(axis_name)),
    )
    return fn_sharded(flat_params, flat_auxs, microbatches)
