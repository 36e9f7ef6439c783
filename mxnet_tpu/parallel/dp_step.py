"""FusedTrainStep: forward + backward + all-reduce + optimizer update in
ONE donated XLA computation.

This is the TPU-native replacement for the reference's training data
plane, where three separate mechanisms cooperate per step:

  - GraphExecutor::Forward/Backward pushes cached engine ops
    (src/executor/graph_executor.cc:780-832),
  - KVStore push/pull wraps ZPush/ZPull in engine async ops so comm
    overlaps compute (src/kvstore/kvstore_dist.h:111-123,
    python/mxnet/model.py:88-97 priority-ordered push/pull),
  - the optimizer runs per-parameter fused kernels
    (src/operator/optimizer_op-inl.h).

Here all three collapse into a single jit: the loss graph's vjp produces
gradients, GSPMD inserts the cross-device all-reduce when the batch is
sharded over a mesh axis (gradients of replicated parameters against a
sharded batch ARE the psum — no host hop, no parameter server), and the
optimizer's traced `apply_dense` updates weights and state in the same
computation. Buffers for parameters, optimizer state, and aux state are
donated, so the update is in-place at the XLA level — the analog of the
reference's PlanMemory/inplace-addto passes.

Mixed precision (the reference trains fp16 via cuDNN,
tests/python/train/test_dtype.py): `compute_dtype=bfloat16` keeps fp32
master weights and casts weights/activations to bf16 for the fwd/bwd
compute; gradient cotangents come back through the cast (fp32), and aux
(e.g. BatchNorm running stats) updates are cast back to their master
dtype. Labels are never cast (class indices above 256 are not bf16-
representable).
"""
from __future__ import annotations

import contextlib
import logging
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import profiler as _profiler
from ..base import MXNetError
from ..ndarray import NDArray


def _to_jnp_tree(tree):
    """Map NDArray leaves of a pytree (None / NDArray / tuple) to jnp."""
    if tree is None:
        return None
    if isinstance(tree, NDArray):
        return tree._data
    if isinstance(tree, (tuple, list)):
        return tuple(_to_jnp_tree(t) for t in tree)
    return jnp.asarray(tree)


class FusedTrainStep:
    """One donated jit over (params, opt_states, auxs).

    Owns the training state while active: parameters, optimizer state and
    aux arrays live as jax Arrays inside this object, and the Module
    flushes them back into executor NDArrays only when a non-fused code
    path (eval forward, get_params, checkpointing) needs them.
    """

    def __init__(self, executor, optimizer, param_names, label_names=(),
                 mesh=None, data_axis="data", compute_dtype=None,
                 param_specs=None, data_specs=None, batch_scale=None,
                 logger=logging, plan=None):
        self._ex = executor
        self._opt = optimizer
        self._logger = logger
        self._mesh = mesh
        self._data_axis = data_axis
        self._plan = plan
        self._param_specs = dict(param_specs or {})
        self._data_specs = dict(data_specs or {})
        self._compute_dtype = (
            jnp.dtype(compute_dtype) if compute_dtype is not None else None
        )

        arg_names = executor._arg_names
        pset = set(param_names)
        self._param_names = [n for n in arg_names if n in pset]
        self._trainable = [
            n for n in self._param_names
            if executor._grad_req.get(n, "null") != "null"
        ]
        self._data_names = [n for n in arg_names if n not in pset]
        self._label_names = set(label_names)
        self._aux_names = list(executor._aux_names)

        # Take over the training state from the executor — as COPIES:
        # step() donates these buffers to XLA, and donating an array the
        # executor/module still references would invalidate it under
        # the caller's feet.
        self.params = {
            n: jnp.copy(executor.arg_dict[n]._data)
            for n in self._param_names
        }
        self.auxs = {
            n: jnp.copy(executor.aux_dict[n]._data)
            for n in self._aux_names
        }
        self.states = {
            n: _to_jnp_tree(
                optimizer.create_state(i, executor.arg_dict[n])
            )
            for i, n in enumerate(self._trainable)
        }
        # MXNET_TPU_OPT_STATE_DTYPE=bfloat16 stores optimizer state
        # (momentum/moments) in bf16: halves the optimizer-update HBM
        # traffic — one of the r3 profile's residual costs — at a small
        # accumulation-precision cost. The update still computes in
        # f32 (bf16 state promotes inside apply_dense) and rounds back
        # on store (_build preserves state dtypes across steps so
        # donation stays type-stable).
        sdt = os.environ.get("MXNET_TPU_OPT_STATE_DTYPE")
        self._state_dtype = jnp.dtype(sdt) if sdt else None
        if self._state_dtype is not None:
            self.states = jax.tree_util.tree_map(
                lambda x: x.astype(self._state_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                self.states)
        self._base_rng = executor._rng
        self._t = 0  # steps taken through this fused step
        self._nproc = jax.process_count()
        # how many per-process batches make one global batch: nproc when
        # the batch shards over a process-spanning data axis, 1 when the
        # mesh is pure model/seq/pipe (every process feeds the identical
        # full batch — standard SPMD replicated-input contract). The
        # Module passes the value from its _multiproc_mesh_plan so ONE
        # decision governs executor shapes, staging, and rescale_grad.
        if batch_scale is not None:
            self._batch_scale = int(batch_scale)
        else:
            self._batch_scale = (
                self._nproc if self._nproc > 1 and mesh is not None
                and data_axis in mesh.axis_names else 1)

        if self._nproc > 1:
            # every process must start from ONE weight lineage (the
            # reference pushes init through the servers for the same
            # reason, kvstore_dist.h Push-on-init); rank 0 wins. Host
            # hop happens once at construction, never per step.
            from jax.experimental import multihost_utils

            self.params = multihost_utils.broadcast_one_to_all(
                jax.tree_util.tree_map(np.asarray, self.params))
            self.auxs = multihost_utils.broadcast_one_to_all(
                jax.tree_util.tree_map(np.asarray, self.auxs))
            self.states = multihost_utils.broadcast_one_to_all(
                jax.tree_util.tree_map(np.asarray, self.states))

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._repl = NamedSharding(mesh, P())
            # default batch sharding: dim 0 over the data axis (absent
            # e.g. on a pure-TP mesh -> replicated batch)
            self._batch_sh = (
                NamedSharding(mesh, P(data_axis))
                if data_axis in mesh.axis_names else self._repl
            )
            self._param_sh = {
                n: NamedSharding(mesh, self._param_specs.get(n, P()))
                for n in self.params
            }
            self._data_sh = {
                n: (NamedSharding(mesh, self._data_specs[n])
                    if n in self._data_specs else None)
                for n in self._data_names
            }
            # fsdp gather-before-use: parameters whose COMPUTE layout
            # differs from storage (the plan's fsdp axis drops inside
            # the step) get pinned via with_sharding_constraint in fwd;
            # its vjp transpose IS the reduce-scatter after grad.
            from ..sharding.lower import gather_shardings

            self._gather_sh = gather_shardings(plan, self._param_specs)
            self.params = {
                n: self._put(v, self._param_sh[n])
                for n, v in self.params.items()
            }
            self.auxs = {
                n: self._put(v, self._repl)
                for n, v in self.auxs.items()
            }
            # optimizer state leaves shaped like the param shard with
            # it; anything else (scalar counters) replicates
            self.states = {
                n: self._place_state(self.states[n], n)
                for n in self.states
            }
        else:
            self._repl = None
            self._batch_sh = None
            self._param_sh = None
            self._data_sh = None
            self._gather_sh = {}

        self._multi_cache = {}     # (k, stacked) -> jitted k-step loop
        self._multi_compiled = {}  # (k, stacked) -> AOT executable
        # numerics sentinel (mxnet_tpu.numerics): when a SentinelSpec
        # is enabled, every step program additionally returns one stats
        # row; rows pile up here DEVICE-side until drain_sentinel()
        self._sentinel = None
        self._sentinel_pending = []   # [(rows (k, C) array, [(t, lr)])]
        self._sentinel_dropped = 0
        self._jitted = self._build()
        self._compiled = None  # AOT executable, built on first run

    def _put(self, value, sharding):
        """Place a host/device value under `sharding`. Multi-process:
        the mesh spans processes, so build the global jax.Array from the
        (identical-everywhere) host value instead of device_put."""
        from .mesh import global_put

        return global_put(value, sharding)

    def _state_sharding(self, state, name):
        """Sharding pytree for one param's optimizer state: leaves with
        the param's shape follow the param's sharding, others replicate."""
        pshape = self.params[name].shape
        psh = self._param_sh[name]
        return jax.tree_util.tree_map(
            lambda leaf: psh if getattr(leaf, "shape", None) == pshape
            else self._repl,
            state,
        )

    def _place_state(self, state, name):
        sh = self._state_sharding(state, name)
        return jax.tree_util.tree_map(self._put, state, sh)

    # ------------------------------------------------------------ build
    def _bucket_plan(self):
        """Static plan for the flat-bucket optimizer update
        (MXNET_TPU_OPT_BUCKET=1), or None when ineligible. Eligible
        when every trainable parameter shares one dtype, one state
        structure, one wd multiplier, and a replicated (or meshless)
        layout — concatenation then changes nothing about the
        elementwise update math."""
        if os.environ.get("MXNET_TPU_OPT_BUCKET", "0") != "1":
            return None
        tr = self._trainable
        if not tr:
            return None
        from jax.sharding import PartitionSpec as P

        if self._mesh is not None and any(
                self._param_specs.get(n, P()) != P() for n in tr):
            self._logger.info(
                "opt bucket disabled: sharded parameters present")
            return None
        dtypes = {self.params[n].dtype for n in tr}
        structs = {jax.tree_util.tree_structure(self.states[n])
                   for n in tr}
        if len(dtypes) > 1 or len(structs) > 1:
            self._logger.info(
                "opt bucket disabled: mixed dtype/state structure "
                "across parameters")
            return None
        segs, off = [], 0
        for n in tr:
            sz = int(np.prod(self.params[n].shape))
            segs.append((n, off, sz))
            off += sz
        return {"segs": segs}

    def _build(self):
        run = self._ex._run_graph
        opt = self._opt
        trainable = list(self._trainable)
        cdt = self._compute_dtype
        labels = self._label_names
        bucket = self._bucket_plan()
        self._bucket_active = bucket is not None
        gsh = self._gather_sh
        mesh = self._mesh
        sentinel = self._sentinel
        nan_inj = self._nan_inject_plan()

        def gather_c(tree):
            """Pin fsdp-stored params to their compute layout inside
            the trace (gather-before-use); the vjp transpose of this
            constraint is the reduce-scatter of the gradients."""
            if not gsh:
                return tree
            from ..sharding.lower import constrain

            return {
                k: (constrain(v, mesh, gsh[k]) if k in gsh else v)
                for k, v in tree.items()
            }

        def cast_c(x):
            """master -> compute dtype (params, auxs, float data).
            UNSIGNED integer data (uint8 raw-pixel batches from the
            iterator's dtype='uint8' path) promotes to the compute
            dtype here, ON DEVICE — the host->device transfer stays
            1/4 size and the cast fuses into the first consumer;
            signed ints (labels, indices) are never touched."""
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(cdt) if cdt is not None else x
            if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
                return x.astype(cdt if cdt is not None else jnp.float32)
            return x

        def step(params, states, auxs, data, lr, t):
            rng = jax.random.fold_in(self._base_rng, t)
            train_p = {k: params[k] for k in trainable}
            frozen_p = {
                k: v for k, v in params.items() if k not in train_p
            }
            data_c = {
                k: (v if k in labels else cast_c(v))
                for k, v in data.items()
            }
            auxs_c = {k: cast_c(v) for k, v in auxs.items()}
            frozen_c = gather_c({k: cast_c(v)
                                 for k, v in frozen_p.items()})

            def fwd(tp):
                tp_c = gather_c({k: cast_c(v) for k, v in tp.items()})
                return run(
                    {**frozen_c, **tp_c, **data_c}, auxs_c, rng, True
                )

            outs, vjp_fn, aux_upd = jax.vjp(fwd, train_p, has_aux=True)
            (grads,) = vjp_fn([jnp.ones_like(o) for o in outs])

            if nan_inj is not None:
                # fault-injection (MXNET_TPU_FAULT_INJECT=nan:step:N):
                # poison one gradient tensor ON DEVICE at step N — a
                # jnp.where on the step counter, baked into the trace,
                # so the injected run compiles the same program shape
                # as a healthy one (no retrace, no host branch)
                iname, istep = nan_inj
                g = grads[iname]
                grads = dict(grads)
                grads[iname] = jnp.where(
                    jnp.equal(t, np.int32(istep)),
                    jnp.full_like(g, jnp.nan), g)

            new_params = dict(params)
            new_states = dict(states)
            keep_dtype = jax.tree_util.tree_map
            if bucket is not None:
                # MXNET_TPU_OPT_BUCKET: ONE apply_dense over every
                # trainable parameter concatenated flat (multi-tensor
                # apply) — identical elementwise math, ~1 fused update
                # kernel instead of one per parameter. lr/wd
                # multipliers are read HERE (trace time, same moment
                # the per-param path reads them) and become
                # per-element vectors when non-uniform — lr and wd
                # enter every registered optimizer elementwise, so a
                # vector broadcasts into the same math.
                segs = bucket["segs"]
                wflat = jnp.concatenate(
                    [params[n].ravel() for n in trainable])
                gflat = jnp.concatenate(
                    [grads[n].astype(params[n].dtype).ravel()
                     for n in trainable])
                sflat = jax.tree_util.tree_map(
                    lambda *leaves: jnp.concatenate(
                        [l.ravel() for l in leaves]),
                    *[states[n] for n in trainable]) \
                    if states[trainable[0]] is not None else None
                lms = [opt._lr_mult_for(n) for n in trainable]
                lr_b = lr
                if any(lm != lms[0] for lm in lms):
                    lr_b = lr * jnp.concatenate([
                        jnp.full((sz,), np.float32(lm))
                        for (_n, _o, sz), lm in zip(segs, lms)])
                elif lms[0] != 1.0:
                    lr_b = lr * np.float32(lms[0])
                wds = [opt._wd_for(n) for n in trainable]
                if opt.wd and any(w != wds[0] for w in wds):
                    wd_mult_vec = jnp.concatenate([
                        jnp.full((sz,), np.float32(w / opt.wd))
                        for (_n, _o, sz), w in zip(segs, wds)])
                else:
                    wd_mult_vec = (wds[0] / opt.wd) if opt.wd else 1.0
                with opt.temp_wd_mult("__bucket__", wd_mult_vec):
                    w2, s2 = opt.apply_dense(
                        "__bucket__", wflat, gflat, sflat, lr_b, t)
                for n, off, sz in bucket["segs"]:
                    shape = params[n].shape
                    new_params[n] = w2[off:off + sz].reshape(shape)
                    if s2 is None:
                        new_states[n] = None
                    else:
                        piece = jax.tree_util.tree_map(
                            lambda leaf, sh=shape, o=off, z=sz:
                            leaf[o:o + z].reshape(sh), s2)
                        new_states[n] = keep_dtype(
                            lambda old, new: new.astype(old.dtype),
                            states[n], piece)
            else:
                for name in trainable:
                    w = params[name]
                    g = grads[name].astype(w.dtype)
                    lr_p = lr * opt._lr_mult_for(name)
                    w2, s2 = opt.apply_dense(
                        name, w, g, states[name], lr_p, t
                    )
                    new_params[name] = w2
                    # preserve the stored state dtype (bf16 opt-state
                    # mode computes in promoted f32, rounds back on
                    # store) so donated buffers stay type-stable
                    new_states[name] = keep_dtype(
                        lambda old, new: new.astype(old.dtype),
                        states[name], s2)
            new_auxs = {
                **auxs,
                **{
                    k: v.astype(auxs[k].dtype)
                    for k, v in aux_upd.items()
                    if k in auxs
                },
            }
            if sentinel is not None:
                # numerics sentinel row: every reduction here happens
                # inside the jit, so under a mesh GSPMD turns them into
                # the cross-shard psums for free and the row comes out
                # replicated — norms are GLOBAL regardless of the plan
                row = sentinel.compute(outs, params, new_params, grads)
                return outs, new_params, new_states, new_auxs, row
            return outs, new_params, new_states, new_auxs

        self._step_fn = step  # raw traceable body (multi-step loop)
        kwargs = {"donate_argnums": (0, 1, 2)}
        if self._mesh is not None:
            state_sh = {
                n: self._state_sharding(self.states[n], n)
                for n in self.states
            }
            aux_sh = {n: self._repl for n in self.auxs}
            data_sh = {
                n: (self._data_sh.get(n) or self._batch_sh)
                for n in self._data_names
            }
            kwargs["in_shardings"] = (
                self._param_sh, state_sh, aux_sh, data_sh, None, None,
            )
            # outputs keep whatever layout XLA picks (batch-sharded in
            # practice); pinning them could fail on rank-0 outputs.
            # Multi-process: replicate outputs (one small all-gather)
            # so every process can read them without a collective fetch
            out_sh = (
                self._repl if self._nproc > 1 else None,
                self._param_sh, state_sh, aux_sh,
            )
            if sentinel is not None:
                out_sh = out_sh + (self._repl,)
            kwargs["out_shardings"] = out_sh
        from ..sharding.lower import jit_sharded

        return jit_sharded(
            step,
            in_shardings=kwargs.get("in_shardings"),
            out_shardings=kwargs.get("out_shardings"),
            donate_argnums=kwargs["donate_argnums"],
            digest=self._profiling_digest(), kind="fused_step")

    def _profiling_digest(self):
        """Executable-accounting key for this step's programs: the
        executor's exec-cache entry digest, plus the sharding-plan
        digest when one governs the layout (the same symbol under two
        plans is two different executables)."""
        digest = getattr(self._ex._compiled, "digest", None)
        if digest and self._plan is not None:
            try:
                digest = f"{digest}+{self._plan.digest()[:8]}"
            except Exception:
                pass
        return digest

    def _nan_inject_plan(self):
        """(param_name, step) for the fault injector's 'nan:step:N'
        spec, or None. Resolved at build time so the poison bakes into
        the trace. Lazy import: fault.py imports the model layer."""
        from ..fault import parse_nan_inject

        spec = parse_nan_inject()
        if spec is None:
            return None
        istep, pname = spec
        if pname is None:
            pname = self._trainable[0] if self._trainable else None
        if pname not in self._trainable:
            self._logger.warning(
                "nan injection target %r is not a trainable parameter "
                "— injection disabled", pname)
            return None
        return (pname, istep)

    # -------------------------------------------------- numerics sentinel
    # device-resident rows between drains are bounded; a run that never
    # drains (numerics enabled, no monitor attached) drops the oldest
    _SENTINEL_CAP = 4096

    def enable_sentinel(self, spec):
        """Bake a numerics SentinelSpec into the step programs: every
        step then returns one extra replicated stats row. Rebuilds the
        jits — cheap before first compile (AOT compilation is lazy),
        a recompile after. Idempotent for the same spec."""
        if self._sentinel is spec:
            return
        self._sentinel = spec
        self._jitted = self._build()
        self._compiled = None
        self._multi_cache.clear()
        self._multi_compiled.clear()

    def _absorb(self, res, meta):
        """Unpack one dispatch's result into the owned training state;
        stash sentinel rows (still ON DEVICE — zero sync) when enabled.
        `meta` is [(t, lr)], one entry per row the result carries."""
        if self._sentinel is None:
            outs, self.params, self.states, self.auxs = res
            return outs
        outs, self.params, self.states, self.auxs, rows = res
        self._sentinel_pending.append((rows, list(meta)))
        total = sum(len(m) for _r, m in self._sentinel_pending)
        while total > self._SENTINEL_CAP and \
                len(self._sentinel_pending) > 1:
            _r, m = self._sentinel_pending.pop(0)
            total -= len(m)
            self._sentinel_dropped += len(m)
        return outs

    @staticmethod
    def _rows_ready(rows):
        try:
            return rows.is_ready()
        except AttributeError:
            return True

    def drain_sentinel(self, wait=True):
        """Move pending sentinel rows to host in ONE fetch (counted in
        hostSyncStats exactly like the device-metric drain, PR 3).
        Returns [(t, lr, row)] with row a 1-D float32 vector in the
        spec's column order; [] (no fetch) when nothing is pending.

        `wait=False` is the steady-state mode (NumericsMonitor's
        interval drains): only rows whose step has already COMPLETED
        on device are fetched, so the drain never stalls the dispatch
        pipeline behind an in-flight step — those rows ride the next
        drain. `wait=True` (epoch ends, manual drains, device Monitor
        toc) blocks for everything pending."""
        pending = self._sentinel_pending
        if not pending:
            return []
        if wait:
            take = len(pending)
        else:
            # dispatch order == completion order: the first unready
            # entry bounds everything after it
            take = 0
            for rows, _m in pending:
                if not self._rows_ready(rows):
                    break
                take += 1
            if take == 0:
                return []
        self._sentinel_pending = pending[take:]
        pending = pending[:take]
        host = jax.device_get([r for r, _m in pending])
        _profiler.count_host_sync("blocking_fetches")
        _profiler.count_host_sync("metric_fetches")
        out = []
        for mat, (_rows, metas) in zip(host, pending):
            mat = np.asarray(mat)
            if mat.ndim == 1:
                mat = mat[None]
            for i, (t, lr) in enumerate(metas):
                out.append((int(t), float(lr), mat[i]))
        return out

    # -------------------------------------------------------------- run
    def _place_data(self, data_vals):
        if self._batch_sh is None:
            return data_vals
        if self._nproc > 1:
            # THE multi-process data plane: each process contributes its
            # local batch shard; the global array is assembled without
            # any host gather, and the gradient all-reduce happens
            # inside the jit over DCN/ICI (vs the reference's
            # engine-wrapped ZPush/ZPull, kvstore_dist.h:111-123)
            return {
                k: jax.make_array_from_process_local_data(
                    self._data_sh.get(k) or self._batch_sh,
                    np.asarray(v))
                for k, v in data_vals.items()
            }
        return {
            k: jax.device_put(v, self._data_sh.get(k) or self._batch_sh)
            for k, v in data_vals.items()
        }

    def _ambient(self):
        """Install this step's mesh as ambient for the trace (mesh-aware
        ops — RingAttention, MoEFFN — read it); no-op without a mesh."""
        from . import mesh as mesh_mod

        return mesh_mod.use_mesh(self._mesh) if self._mesh is not None \
            else contextlib.nullcontext()

    def step(self, data_vals):
        """Run one fused step on {name: jnp array} batch inputs. Returns
        the forward outputs; params/states/auxs are advanced in place."""
        self._t += 1
        opt = self._opt
        opt.num_update += 1
        lr = (
            opt.lr_scheduler(opt.num_update)
            if opt.lr_scheduler is not None else opt.lr
        )
        args = (
            self.params, self.states, self.auxs,
            self._place_data(data_vals),
            np.float32(lr), np.int32(self._t),
        )
        with self._ambient(), _profiler.scope(
                "fused_train_step", "executor"):
            if self._compiled is None:
                try:
                    self._compiled = self._jitted.lower(*args).compile()
                except Exception:  # fall back to dispatch-compiled jit
                    self._compiled = False
            fn = self._compiled if self._compiled else self._jitted
            meta = ((self._t, float(lr)),)
            try:
                outs = self._absorb(fn(*args), meta)
            except (TypeError, ValueError):
                # shape/dtype drift (e.g. a differently-sized final
                # batch): the AOT executable is exact-shape; re-dispatch
                outs = self._absorb(self._jitted(*args), meta)
        return outs

    # ------------------------------------------------- multi-step loop
    def _multi_fn(self, k, stacked):
        """jit of a device-side k-step training loop (lax.scan over the
        fused step body). One host dispatch advances k optimizer steps,
        so the per-dispatch host cost amortizes k-fold. The reference gets
        the same effect from its async dependency engine queueing many
        ops ahead of the host (SURVEY §2.2); the XLA-native equivalent
        is a compiled step loop."""
        key = (int(k), bool(stacked))
        fn = self._multi_cache.get(key)
        if fn is not None:
            return fn
        step_fn = self._step_fn
        sentinel = self._sentinel

        def multi(params, states, auxs, data, lrs, ts):
            carry = (params, states, auxs)
            rows = None
            if k > 1:
                if stacked:
                    xs = ({n: v[:-1] for n, v in data.items()},
                          lrs[:-1], ts[:-1])

                    def body(c, x):
                        data_i, lr_i, t_i = x
                        p, s, a = c
                        res = step_fn(p, s, a, data_i, lr_i, t_i)
                        return (res[1], res[2], res[3]), \
                            (res[4] if sentinel is not None else None)
                else:
                    xs = (lrs[:-1], ts[:-1])

                    def body(c, x):
                        lr_i, t_i = x
                        p, s, a = c
                        res = step_fn(p, s, a, data, lr_i, t_i)
                        return (res[1], res[2], res[3]), \
                            (res[4] if sentinel is not None else None)
                carry, rows = jax.lax.scan(body, carry, xs)
            params, states, auxs = carry
            last = {n: v[-1] for n, v in data.items()} if stacked \
                else data
            res = step_fn(params, states, auxs, last, lrs[-1], ts[-1])
            if sentinel is None:
                return res
            outs, p2, s2, a2, last_row = res
            # (k, C) row matrix: scan ys for the first k-1 steps plus
            # the peeled final step — same drain shape as k step()s
            all_rows = (jnp.concatenate([rows, last_row[None]], 0)
                        if rows is not None else last_row[None])
            return outs, p2, s2, a2, all_rows

        kwargs = {"donate_argnums": (0, 1, 2)}
        if self._mesh is not None:
            state_sh = {
                n: self._state_sharding(self.states[n], n)
                for n in self.states
            }
            aux_sh = {n: self._repl for n in self.auxs}
            base_sh = {
                n: (self._data_sh.get(n) or self._batch_sh)
                for n in self._data_names
            }
            data_sh = base_sh if not stacked else {
                n: NamedSharding(self._mesh, P(None, *sh.spec))
                for n, sh in base_sh.items()
            }
            kwargs["in_shardings"] = (
                self._param_sh, state_sh, aux_sh, data_sh, None, None,
            )
            out_sh = (
                self._repl if self._nproc > 1 else None,
                self._param_sh, state_sh, aux_sh,
            )
            if sentinel is not None:
                out_sh = out_sh + (self._repl,)
            kwargs["out_shardings"] = out_sh
        from ..sharding.lower import jit_sharded

        fn = jit_sharded(
            multi,
            in_shardings=kwargs.get("in_shardings"),
            out_shardings=kwargs.get("out_shardings"),
            donate_argnums=kwargs["donate_argnums"],
            digest=self._profiling_digest(),
            kind=f"fused_multi[{int(k)}]")
        self._multi_cache[key] = fn
        return fn

    def run_steps(self, data_vals, k, stacked=False):
        """Advance k train steps in ONE dispatch. Semantically identical
        to k ``step()`` calls: per-step lr follows the scheduler, t (and
        therefore the dropout rng chain) advances per inner step, state
        dtypes are preserved by the body itself.

        stacked=False reuses one resident batch for every inner step
        (synthetic benchmarking); stacked=True expects every data value
        with a leading (k,) axis of per-step batches and scans over it.

        Multi-process meshes run the SAME compiled k-loop for stacked
        batches: each process contributes its local (k, local_rows,
        ...) slice and the global array assembles without a host
        gather, exactly like the single-step data plane (_place_data).
        The non-stacked (replayed-batch) form stays sequential there —
        it exists for single-host benching only."""
        if k < 1:
            raise ValueError("run_steps needs k >= 1")
        opt = self._opt
        lrs, ts = [], []
        for _ in range(k):
            self._t += 1
            opt.num_update += 1
            lrs.append(float(
                opt.lr_scheduler(opt.num_update)
                if opt.lr_scheduler is not None else opt.lr))
            ts.append(self._t)
        if self._nproc > 1 and not stacked:
            outs = None
            placed = self._place_data(data_vals)  # loop-invariant
            for i in range(k):
                args = (self.params, self.states, self.auxs, placed,
                        np.float32(lrs[i]), np.int32(ts[i]))
                with self._ambient():
                    outs = self._absorb(
                        self._jitted(*args), ((ts[i], lrs[i]),))
            return outs
        lrs_v = np.asarray(lrs, np.float32)
        ts_v = np.asarray(ts, np.int32)

        def stacked_sharding(n):
            return NamedSharding(
                self._mesh,
                P(None, *(self._data_sh.get(n)
                          or self._batch_sh).spec))

        if stacked and self._nproc > 1:
            # global (k, global_rows, ...) from per-process local
            # slices — the multi-process data plane, leading step
            # axis replicated
            data = {
                n: jax.make_array_from_process_local_data(
                    stacked_sharding(n), np.asarray(v))
                for n, v in data_vals.items()
            }
        elif stacked and self._mesh is not None:
            data = {
                n: jax.device_put(v, stacked_sharding(n))
                for n, v in data_vals.items()
            }
        elif stacked:
            data = data_vals
        else:
            data = self._place_data(data_vals)
        fn = self._multi_fn(k, stacked)
        key = (int(k), bool(stacked))
        with self._ambient(), _profiler.scope(
                "fused_train_steps", "executor"):
            args = (self.params, self.states, self.auxs,
                    data, lrs_v, ts_v)
            ex = self._multi_compiled.get(key)
            if ex is None:
                try:  # AOT, like the single-step path
                    ex = fn.lower(*args).compile()
                except Exception:
                    ex = False
                self._multi_compiled[key] = ex
            call = ex if ex else fn
            meta = tuple(zip(ts, lrs))
            try:
                outs = self._absorb(call(*args), meta)
            except (TypeError, ValueError):
                outs = self._absorb(fn(*args), meta)
        return outs

    def sync(self):
        """Fence: wait until all queued steps have executed.

        block_until_ready on every parameter, then a host fetch of
        one parameter element: a value that reached the host cannot
        have been merely enqueued, whatever the backend's
        block_until_ready acknowledges (chip_smoke.py times both)."""
        _profiler.count_host_sync("blocking_waits")
        jax.block_until_ready(self.params)
        if self.params:
            leaf = next(iter(self.params.values()))
            if self._nproc > 1:
                np.asarray(leaf.addressable_data(0))
            else:
                np.asarray(jax.device_get(jnp.ravel(leaf)[0]))

    # --------------------------------------------------------- teardown
    def load_params(self, arg_params, aux_params):
        """Replace the owned parameters/auxs from NDArray dicts (the
        Module calls this when params changed outside the fused step —
        set_params, init_params(force_init), an eager update)."""
        def place(x, sh):
            if sh is not None:
                return self._put(np.asarray(x), sh)
            return jnp.copy(jnp.asarray(x))

        for n in self._param_names:
            sh = self._param_sh[n] if self._param_sh is not None else None
            self.params[n] = place(arg_params[n]._data, sh)
        for n in self._aux_names:
            self.auxs[n] = place(aux_params[n]._data, self._repl)

    def snapshot(self):
        """(params, auxs) as safe-to-expose copies: the live buffers
        will be donated by the next step(), so callers must never hold
        references to them. In mesh mode the copies are materialized on
        a single device so eager executors can consume them.

        Multi-process with model-sharded params this is COLLECTIVE
        (full_host all-gathers): every process must reach it — get_params
        / checkpointing must not be rank-guarded (jax multihost
        contract; the reference's rank-0-only save worked because dist
        kvstore values were always replicated)."""
        if self._mesh is None:
            leaf = jnp.copy
        elif self._nproc > 1:
            # replicated leaves read their local copy; model-sharded
            # params all-gather to replicated first (full_host)
            from .mesh import full_host

            leaf = lambda v: jnp.asarray(full_host(v))
        else:
            dev0 = self._mesh.devices.flat[0]
            leaf = lambda v: jax.device_put(v, dev0)
        cp = lambda t: {k: leaf(v) for k, v in t.items()}
        return cp(self.params), cp(self.auxs)

    # ------------------------------------------------------ diagnostics
    def flops(self):
        """FLOPs of one compiled train step, from XLA cost analysis.

        When only a multi-step loop was compiled (run_steps-only use,
        steps_per_dispatch > 1), per-step work is estimated from the
        k-loop program. XLA cost analysis counts a while/scan body ONCE
        regardless of trip count, so the k-loop program's reported cost
        is (scan body) + (the one peeled final step) ~= 2x one step for
        any k > 1 — hence the /2 below (exactly 1x for k == 1, where
        there is no scan). The residual error is the non-step scan
        plumbing, which is negligible against a train step."""
        def _cost(ex):
            cost = ex.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            return float(cost.get("flops", 0.0))

        try:
            if self._compiled:
                return _cost(self._compiled)
            for (k, _st), ex in self._multi_compiled.items():
                if ex:
                    return _cost(ex) / (2.0 if k > 1 else 1.0)
        except Exception:
            return 0.0
        return 0.0

    # ------------------------------------------ optimizer state save/load
    STATE_FORMAT = "mxnet_tpu/fused_v1"

    def get_states(self):
        # collective when states are model-sharded multi-process: all
        # processes must call (see snapshot's contract note)
        from .mesh import full_host

        host = jax.tree_util.tree_map(full_host, self.states)
        return pickle.dumps(
            {"format": self.STATE_FORMAT, "t": self._t, "states": host}
        )

    def set_states(self, blob):
        obj = pickle.loads(blob)
        if isinstance(obj, dict) and obj.get("format") == \
                self.STATE_FORMAT:
            t, host = obj["t"], obj["states"]
        elif isinstance(obj, dict):
            # eager Updater checkpoint ({index: state}): translate
            # indices to parameter names through the optimizer's map
            idx2name = self._opt.idx2name
            host = {
                idx2name[i]: v for i, v in obj.items()
                if idx2name.get(i) in self.states
            }
            missing = set(self.states) - set(host)
            if missing:
                raise MXNetError(
                    f"optimizer state file lacks entries for {missing}"
                )
            t = self._opt.num_update
        else:
            raise MXNetError("unrecognized optimizer state format")

        tmpl = self.states
        new = jax.tree_util.tree_map(jnp.asarray, host)
        if self._state_dtype is not None:
            # a resumed f32 checkpoint must re-enter the configured
            # reduced-precision state mode, not silently disable it
            new = jax.tree_util.tree_map(
                lambda x: x.astype(self._state_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, new)
        if self._repl is not None:
            new = {n: self._place_state(s, n) for n, s in new.items()}
        if jax.tree_util.tree_structure(new) != \
                jax.tree_util.tree_structure(tmpl):
            raise MXNetError("optimizer state structure mismatch")
        self._t = t
        self.states = new


def supports_fused(optimizer):
    """True when the optimizer overrides the traced apply_dense form."""
    from ..optimizer import Optimizer

    return type(optimizer).apply_dense is not Optimizer.apply_dense
