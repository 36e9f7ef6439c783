"""Executor: binds a Symbol + NDArrays into a compiled computation.

Analog of the reference GraphExecutor (src/executor/graph_executor.cc:333
Init / :912 Bind) and python/mxnet/executor.py. The entire NNVM pass
pipeline collapses into XLA:

  Gradient pass            -> jax.vjp over the traced graph
  PlaceDevice              -> sharding annotations (parallel/, later)
  InferShape/InferType     -> done at bind via ops/shape_infer.py
  PlanMemory / inplace     -> XLA buffer assignment + donation
  AttachOpExecs, bulk-exec -> ONE jit computation for the whole graph
                              (the logical endpoint of bulk-exec: the
                              "segment" is the entire graph)

Training uses a single fused forward+backward computation: `forward
(is_train=True)` runs it with default head gradients (ones — loss ops'
custom_vjp ignores/replaces them, matching reference semantics), caches
gradients, and `backward()` just applies them to the grad arrays under
grad_req write/add. An explicit `backward(out_grads)` re-runs the fused
computation with the provided head gradients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import exec_cache as _exec_cache
from . import profiler as _profiler
from . import random as _random
from .base import MXNetError
from .exec_cache import cache_stats  # noqa: F401  (public API)
from .ndarray import NDArray
from .symbol import _topo


class Executor:
    """A Symbol bound to devices and arrays, runnable forward/backward.

    The whole graph traces into ONE jit computation with `jax.vjp` as
    the Gradient pass (reference GraphExecutor,
    src/executor/graph_executor.cc); surface: forward/backward/
    outputs/arg_dict/reshape/monitor."""

    def __init__(self, symbol, ctx, args, args_grad, grad_req, aux_states,
                 group2ctx=None, shared_exec=None, sharding=None):
        self._symbol = symbol
        self._ctx = ctx
        self._group2ctx = group2ctx or {}
        # `sharding` is a ShardingPlan (or None): its digest joins the
        # exec-cache key below, so rebinding one symbol under a
        # different plan never lands on a compiled program whose
        # in/out shardings were baked for another mesh/rule set
        self._sharding_plan = sharding
        self.arg_dict = dict(args)
        self.grad_dict = dict(args_grad or {})
        self.aux_dict = dict(aux_states or {})
        self._grad_req = dict(grad_req)
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        missing = [n for n in self._arg_names if n not in self.arg_dict]
        if missing:
            raise MXNetError(f"bind: missing arguments {missing}")
        self.arg_arrays = [self.arg_dict[n] for n in self._arg_names]
        self.grad_arrays = [
            self.grad_dict.get(n) for n in self._arg_names
        ]
        self.aux_arrays = [self.aux_dict[n] for n in self._aux_names]
        self._grad_names = [
            n
            for n in self._arg_names
            if self._grad_req.get(n, "null") != "null" and n in self.grad_dict
        ]
        # Allocate output NDArrays at bind time (the reference GraphExecutor
        # allocates head entries in InitDataEntryMemory, so exec.outputs is
        # valid before the first Forward — SequentialModule relies on this).
        _, out_shapes, _ = symbol.infer_shape(
            **{n: tuple(a.shape) for n, a in self.arg_dict.items()}
        )
        if out_shapes is None:
            raise MXNetError(
                f"bind: cannot infer output shapes for {symbol.list_outputs()}"
            )
        try:
            _, out_types, _ = symbol.infer_type()
        except Exception:
            out_types = None
        if not out_types:
            out_types = [np.float32] * len(out_shapes)
        self.outputs = [
            NDArray(jnp.zeros(s, t), ctx=ctx)
            for s, t in zip(out_shapes, out_types)
        ]
        self._monitor_callback = None
        self._cached_grads = None
        self._last_inputs = None
        # draw from the framework PRNG chain so mx.random.seed() controls
        # symbolic Dropout/rrelu reproducibly
        self._rng = _random.next_key()

        self._build(shared_exec)

    # ----------------------------------------------------------- build
    def _build(self, shared_exec=None):
        """Resolve this bind to a CompiledGraph: an exec_cache lookup
        keyed by the canonical graph signature + shapes/dtypes/grad
        config. A shared_exec with a matching signature short-circuits
        the table (the reference's shared-executor bind); otherwise a
        hit shares the previously traced program and a miss traces a
        new one."""
        import os as _os

        from . import passes as _passes
        from .analysis import graph_verify as _gv

        if _gv.verify_enabled():
            _gv.verify_graph(
                self._symbol,
                grad_names=self._grad_names,
                **{n: tuple(a.shape)
                   for n, a in {**self.arg_dict,
                                **self.aux_dict}.items()})

        # graph-pass pipeline (MXNET_GRAPH_PASSES, memoized): the
        # executor TRACES the optimized graph but keeps the original
        # symbol as its public surface (arg names, output names,
        # infer_shape) — passes never rename variables, so binding
        # stays by-name against the same buffers. The cache key is
        # built from the OPTIMIZED canonical graph: isomorphic
        # differently-built symbols collapse onto one entry.
        self._opt_symbol = _passes.optimize_for_bind(self._symbol)
        raw_key = self._symbol.structure_key()
        graph_key = (raw_key if self._opt_symbol is self._symbol
                     else self._opt_symbol.structure_key())

        mirror = _os.environ.get(
            "MXNET_BACKWARD_DO_MIRROR", "0") not in ("0", "", "false")
        self._cache_key = (
            graph_key,
            tuple(sorted(
                (g, repr(c)) for g, c in self._group2ctx.items())),
            tuple((n, tuple(self.arg_dict[n].shape),
                   str(self.arg_dict[n].dtype))
                  for n in self._arg_names),
            tuple((n, tuple(self.aux_dict[n].shape),
                   str(self.aux_dict[n].dtype))
                  for n in self._aux_names),
            tuple((n, self._grad_req.get(n, "null"))
                  for n in self._arg_names),
            tuple(self._grad_names),
            (self._sharding_plan.digest()
             if self._sharding_plan is not None else None),
            mirror,
        )
        # HBM pre-flight BEFORE any program is looked up or traced:
        # strict mode turns an over-cap bind into an exception with
        # zero traces executed (mxnet_tpu.profiling.preflight)
        from . import profiling as _profiling

        if _profiling.profiling_enabled():
            try:
                _profiling.preflight_bind(
                    self._opt_symbol,
                    {n: (tuple(a.shape), a.dtype)
                     for n, a in self.arg_dict.items()},
                    self._grad_req,
                    auxs={n: (tuple(a.shape), a.dtype)
                          for n, a in self.aux_dict.items()},
                    plan=self._sharding_plan)
            except _profiling.HBMPreflightError:
                raise
            except Exception:
                pass  # estimation failure must never block a bind

        if (shared_exec is not None
                and getattr(shared_exec, "_cache_key", None)
                == self._cache_key
                and getattr(shared_exec, "_compiled", None) is not None):
            self._compiled = shared_exec._compiled
            _exec_cache.count_shared_hit()
            return
        self._compiled = _exec_cache.lookup_or_build(
            self._cache_key, self._trace_graph,
            raw_sig=hash(raw_key),
            canonical_fn=lambda: _passes.canonical_digest(
                self._opt_symbol),
            disk_meta_fn=self._disk_record_meta)

    def _disk_record_meta(self):
        """What the disk tier (exec_cache_disk) persists alongside the
        entry digest: the OPTIMIZED canonical graph JSON plus the full
        bind signature — enough to inspect/rebuild the program offline
        (tools/mx_bundle.py inspect) without re-running the passes."""
        return {
            # _opt_symbol already went through the bind-time pipeline
            # (or the user turned it off) — plain serialization, so
            # the record write never re-runs passes or bills
            # pipeline_runs for key/metadata work
            "graph_json": self._opt_symbol.tojson(),
            "inputs": [[n, list(self.arg_dict[n].shape),
                        str(self.arg_dict[n].dtype)]
                       for n in self._arg_names],
            "auxs": [[n, list(self.aux_dict[n].shape),
                      str(self.aux_dict[n].dtype)]
                     for n in self._aux_names],
            "grad_req": {n: self._grad_req.get(n, "null")
                         for n in self._arg_names},
            "sharding": (self._sharding_plan.digest()
                         if self._sharding_plan is not None else None),
        }

    def _trace_graph(self):
        """Build the pure run_graph program + node plan for this bind's
        signature (cache-miss path). No jax tracing happens here — each
        per-mode jit is constructed lazily by CompiledGraph and traces
        on its first call."""
        sym = getattr(self, "_opt_symbol", None) or self._symbol
        nodes = _topo(sym._outputs)
        node_ids = {id(n): i for i, n in enumerate(nodes)}
        heads = [(id(n), i) for n, i in sym._outputs]
        # ctx-group model parallelism (reference PlaceDevice pass +
        # __ctx_group__ attrs, graph_executor.cc:242-318): map each
        # node's group to a concrete device; run_graph inserts
        # device_put at group boundaries — the _CrossDeviceCopy analog,
        # expressed as sharding annotations inside the single jit
        # computation instead of graph surgery.
        group_dev = {
            g: c.jax_device() for g, c in self._group2ctx.items()
        }
        plan = []
        for n in nodes:
            if n.is_variable:
                continue
            params = n.op.normalize_params(n.attrs)
            grp = n._extra_attrs.get("__ctx_group__")
            plan.append(
                (
                    n.op,
                    params,
                    n.op.resolved_num_outputs(params),
                    [(id(src), i) for src, i in n.inputs],
                    id(n),
                    node_ids[id(n)],
                    n.name,
                    group_dev.get(grp),
                )
            )
        var_names = {
            id(n): n.name for n in nodes if n.is_variable
        }
        aux_set = set(self._aux_names)

        def run_graph(arg_vals, aux_vals, rng, is_train):
            _exec_cache.note_graph_replay()
            env = {}
            for nid, name in var_names.items():
                env[(nid, 0)] = (
                    aux_vals[name] if name in aux_set else arg_vals[name]
                )
            aux_updates = {}
            for (opdef, params, n_out, in_keys, nid, node_idx, nname,
                 dev) in plan:
                in_vals = [env[k] for k in in_keys]
                if dev is not None:
                    in_vals = [
                        jax.device_put(v, dev) for v in in_vals
                    ]
                kwargs = dict(params)
                if opdef.needs_rng:
                    kwargs["rng"] = jax.random.fold_in(rng, node_idx)
                if opdef.needs_mode:
                    kwargs["is_train"] = is_train
                # named_scope stamps the node name into HLO
                # op_metadata, which the XLA device trace copies into
                # its event args — profiling.timeline attributes
                # device time back to graph nodes through it. Pure
                # trace-time cost; compiled code is unchanged.
                with jax.named_scope(nname):
                    res = opdef.fn(*in_vals, **kwargs)
                if not isinstance(res, tuple):
                    res = (res,)
                for i in range(n_out):
                    env[(nid, i)] = res[i]
                n_aux = len(opdef.aux_names)
                if n_aux and is_train and len(res) > n_out:
                    # trailing inputs are the aux vars; map updates back
                    for (src, _), upd in zip(
                        in_keys[-n_aux:], res[n_out:]
                    ):
                        aux_updates[var_names[src]] = upd
            outs = [env[k] for k in heads]
            return outs, aux_updates

        # memory mirror: rematerialize forward activations in backward
        # instead of keeping them — jax.checkpoint is the analog of the
        # reference's MXNET_BACKWARD_DO_MIRROR / memonger (trades ~10%
        # speed for much smaller activation memory,
        # example/image-classification/README.md:352-359). Full
        # in-place donation of params+state lives on the fused train
        # step (parallel/dp_step.py), which owns its buffers.
        return _exec_cache.CompiledGraph(
            run_graph, plan, var_names, aux_set,
            grad_names=self._grad_names, mirror=self._cache_key[-1],
        )

    # Compiled-program views (shared via exec_cache; the underscore
    # names are the pre-cache attribute surface other layers use —
    # pipeline_module, dp_step, tests).
    @property
    def _run_graph(self):
        return self._compiled.run_graph

    @property
    def _plan(self):
        return self._compiled.plan

    @property
    def _var_names(self):
        return self._compiled.var_names

    @property
    def _aux_set(self):
        return self._compiled.aux_set

    @property
    def _jit_train_step(self):
        return self._compiled.jit_train_step()

    # --------------------------------------------------------- running
    def _gather_inputs(self):
        arg_vals = {n: self.arg_dict[n]._data for n in self._arg_names}
        aux_vals = {n: self.aux_dict[n]._data for n in self._aux_names}
        return arg_vals, aux_vals

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"unknown forward argument {k!r}")
            self.arg_dict[k][:] = v
        arg_vals, aux_vals = self._gather_inputs()
        self._rng, rng = jax.random.split(self._rng)
        if self._monitor_callback is not None:
            # monitored (debug) path: eager per-node execution so the
            # callback sees every intermediate (reference
            # MXExecutorSetMonitorCallback + ExecuteMonCallback,
            # graph_executor.cc:758). Not jit'd by design. Uses the SAME
            # key as the jit pass below so monitored statistics of
            # stochastic ops (Dropout) reflect the executed draw.
            self._forward_monitored(is_train, rng, arg_vals, aux_vals)
        self._cached_grads = None
        with _profiler.scope(
            f"executor_forward[{'train' if is_train else 'eval'}]",
            "executor",
        ):
            if is_train and self._grad_names:
                head_grads = self._default_head_grads(
                    arg_vals, aux_vals, rng
                )
                outs, grads, aux_upd = self._compiled.jit_train_step()(
                    arg_vals, aux_vals, rng, head_grads
                )
                self._cached_grads = grads
            else:
                outs, aux_upd = self._compiled.jit_fwd(is_train)(
                    arg_vals, aux_vals, rng
                )
        self._last_inputs = (arg_vals, aux_vals, rng)
        if is_train:
            for name, val in aux_upd.items():
                self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        return self.outputs

    def _forward_monitored(self, is_train, rng, arg_vals, aux_vals):
        """Eager per-node execution invoking the monitor callback with
        every node output (debug path; see forward()). `rng` is the
        same key the jit forward will use."""
        env = {}
        for nid, name in self._var_names.items():
            env[(nid, 0)] = (
                aux_vals[name] if name in self._aux_set
                else arg_vals[name]
            )
        for (opdef, params, n_out, in_keys, nid, node_idx, nname,
             dev) in self._plan:
            in_vals = [env[k] for k in in_keys]
            if dev is not None:
                in_vals = [jax.device_put(v, dev) for v in in_vals]
            kwargs = dict(params)
            if opdef.needs_rng:
                kwargs["rng"] = jax.random.fold_in(rng, node_idx)
            if opdef.needs_mode:
                kwargs["is_train"] = bool(is_train)
            res = opdef.fn(*in_vals, **kwargs)
            if not isinstance(res, tuple):
                res = (res,)
            for i in range(n_out):
                env[(nid, i)] = res[i]
                out_name = (
                    f"{nname}_output" if n_out == 1
                    else f"{nname}_output{i}"
                )
                self._monitor_callback(
                    out_name, NDArray(res[i], ctx=self._ctx)
                )

    def _default_head_grads(self, arg_vals, aux_vals, rng):
        # ones-buffers are cached on the shared CompiledGraph and only
        # reallocated when the previous step actually donated them away
        return self._compiled.default_head_grads(arg_vals, aux_vals, rng)

    def backward(self, out_grads=None):
        if not self._grad_names:
            return
        if out_grads is not None:
            if self._last_inputs is None:
                raise MXNetError("backward called before forward")
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            # the train-step jit donates its head-grad buffers only on
            # backends where donation is real — copy just there, so the
            # caller's NDArrays stay valid without paying a copy on
            # donation-free backends
            if _exec_cache.donation_effective():
                head_grads = [jnp.copy(g._data) for g in out_grads]
            else:
                head_grads = [g._data for g in out_grads]
            arg_vals, aux_vals, rng = self._last_inputs
            _, grads, _ = self._compiled.jit_train_step()(
                arg_vals, aux_vals, rng, head_grads
            )
        else:
            if self._cached_grads is None:
                raise MXNetError(
                    "backward called without forward(is_train=True)"
                )
            grads = self._cached_grads
        for name, g in grads.items():
            req = self._grad_req.get(name, "null")
            tgt = self.grad_dict.get(name)
            if tgt is None or req == "null":
                continue
            if req == "write":
                tgt._set_data(g)
            elif req == "add":
                tgt._set_data(tgt._data + g)
            else:
                raise MXNetError(f"unknown grad_req {req!r}")

    # --------------------------------------------------------- utilities
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                self.arg_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError(f"unknown argument {name!r}")
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                self.aux_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError(f"unknown aux state {name!r}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False,
                **new_shapes):
        """Return a new executor bound with new input shapes, sharing
        parameter NDArrays where shapes are unchanged
        (reference MXExecutorReshape)."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**new_shapes)
        from . import ndarray as nd

        new_args = {}
        for name, shape in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if tuple(cur.shape) == tuple(shape):
                new_args[name] = cur
            else:
                new_args[name] = nd.zeros(shape, ctx=self._ctx,
                                          dtype=cur.dtype)
        new_grads = {}
        for name, cur in self.grad_dict.items():
            if name not in self._arg_names:
                # a grad buffer for a name the symbol does not take
                # (user-supplied extras) — carry it over untouched
                # instead of crashing on .index()
                new_grads[name] = cur
                continue
            shape = arg_shapes[self._arg_names.index(name)]
            if tuple(cur.shape) == tuple(shape):
                new_grads[name] = cur
            else:
                new_grads[name] = nd.zeros(shape, ctx=self._ctx,
                                           dtype=cur.dtype)
        new_aux = {}
        for name, shape in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[name]
            if tuple(cur.shape) == tuple(shape):
                new_aux[name] = cur
            else:
                new_aux[name] = nd.zeros(shape, ctx=self._ctx,
                                         dtype=cur.dtype)
        # shared_exec=self: a reshape back to previously-seen shapes
        # resolves in the exec_cache (or directly against this
        # executor) with zero retraces
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, new_aux,
                        group2ctx=self._group2ctx, shared_exec=self)

    def release_arrays(self):
        """Drop all buffer references (args/grads/auxs/outputs), keeping
        only the traced graph. Used by the fused train step, which owns
        its own copies of the training state — without this, parameters
        and gradients would stay resident an extra time."""
        self.arg_dict = {}
        self.grad_dict = {}
        self.aux_dict = {}
        self.arg_arrays = []
        self.grad_arrays = []
        self.aux_arrays = []
        self.outputs = []

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def debug_str(self):
        return self._symbol.debug_str()
