"""Module: Symbol + contexts -> trainable model.

Analog of python/mxnet/module/module.py (Module at :22, update routing at
:553-561). Binds a DataParallelExecutorGroup over the context list; with
a KVStore('tpu') the per-device copies collapse onto the mesh (see
parallel/) but the Module API is identical.
"""
from __future__ import annotations

import logging

from .. import context as ctx
from .. import metric as _metric
from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError
from ..initializer import Uniform, InitDesc
from ..io import DataDesc
from ..model import (
    _create_kvstore,
    _initialize_kvstore,
    _update_params,
    _update_params_on_kvstore,
    load_checkpoint,
    save_checkpoint,
)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    """The workhorse trainer for one Symbol: bind/init/fit plus the
    fused donated train step, mesh sharding (mesh_shape=...), and the
    compiled k-step loop (run_steps / fit(steps_per_dispatch=k))
    (reference module/module.py:22-80)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None,
                 mesh_shape=None, data_shardings=None, sharding=None):
        """`mesh_shape` ({axis: size}, e.g. {'data': 2, 'seq': 4})
        trains through ONE jit over that device mesh: the batch shards
        over 'data', parameters follow their Symbol `__sharding__`
        attrs (PartitionSpec syntax, parallel/mesh.py
        parse_partition_spec), and mesh-aware ops (RingAttention,
        MoEFFN) see the mesh — the TPU-native form of the reference's
        ctx-group model parallelism (example/model-parallel-lstm).
        `data_shardings` ({input_name: spec}) overrides per-input batch
        sharding, e.g. {'data': 'data,seq'} for sequence parallelism.

        `sharding` is a `mxnet_tpu.sharding.ShardingPlan`: mesh AND
        per-parameter-name PartitionSpec rules in one object
        (docs/sharding.md). It subsumes mesh_shape (the plan's mesh
        wins) and composes with Symbol `__sharding__` attrs — explicit
        plan overrides > symbol attrs > plan default rules.
        """
        super().__init__(logger=logger)
        self._sharding_plan = sharding
        if sharding is not None:
            if mesh_shape and dict(mesh_shape) != sharding.axis_sizes:
                logger.warning(
                    "both mesh_shape %s and a sharding plan (mesh %s) "
                    "given; the plan's mesh wins", dict(mesh_shape),
                    sharding.axis_sizes)
            mesh_shape = sharding.axis_sizes
        self._mesh_shape = dict(mesh_shape) if mesh_shape else None
        self._data_shardings = dict(data_shardings or {})

        if context is None:
            context = ctx.current_context()
        if isinstance(context, ctx.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol

        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = (
            list(fixed_param_names) if fixed_param_names is not None else []
        )

        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None

        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

        # fused train step (parallel/dp_step.py): one donated jit for
        # forward+backward+update; None -> eager executor-group path
        self._fused_step = None
        self._fused_dirty = False
        self._fused_stale = False
        # optimizer-state lineage across the fused/eager boundary:
        # _eager_seed_t = fused step count last handed to the eager
        # updater; _opt_state_bifurcated = eager updates ran since the
        # fused step last (re)loaded state
        self._eager_seed_t = 0
        self._opt_state_bifurcated = False
        self._compute_dtype = None
        self._staged_batch = None
        self._staged_vals = None
        self._staged_outputs = None
        self._staged_backward = False
        self._monitor = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """(reference module/module.py:95)"""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """(reference module/module.py:125)"""
        self._symbol.save(f"{prefix}-symbol.json")
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    # ------------------------------------------------------- internal
    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    # ------------------------------------------------------ properties
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return self._exec_group.get_output_shapes()

    # ------------------------------------------------------- parameters
    def get_params(self):
        """(reference module/module.py:183)"""
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """Fill parameters: values come from the given dicts when
        present, from the initializer otherwise (reference
        module/module.py:198-260 semantics)."""
        if self.params_initialized and not force_init:
            logging.warning(
                "Parameters already initialized and force_init=False. "
                "init_params call ignored.")
            return
        if not self.binded:
            raise MXNetError(
                "call bind before initializing the parameters")
        # params the fused step trained but never flushed must land in
        # _arg_params first: entries missing from the given dicts keep
        # their trained values rather than reverting to stale copies
        self._flush_fused()

        attrs = self._symbol.attr_dict()
        changed = False

        def fill(table, source):
            nonlocal changed
            for name, arr in table.items():
                given = None if source is None else source.get(name)
                if given is not None:
                    if given is not arr:
                        given.copyto(arr)
                        changed = True
                    continue
                if source is not None and not allow_missing:
                    raise RuntimeError(f"{name} is not presented")
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name)), arr)
                    changed = True

        fill(self._arg_params, arg_params)
        fill(self._aux_params, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        if self._fused_step is not None and changed:
            # values actually moved (fit()'s epoch-end no-op
            # get_params/set_params round-trip must NOT force a full
            # reload into the fused step)
            self._fused_dirty = False  # fused content superseded
            self._fused_stale = True

        # copy the initialized parameters to devices
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        """Directly assign parameters without initializer (reference
        module/module.py:262-300)."""
        if not allow_missing:
            self.init_params(
                initializer=None, arg_params=arg_params,
                aux_params=aux_params, allow_missing=allow_missing,
                force_init=force_init,
            )
            return
        if self.params_initialized and not force_init:
            logging.warning(
                "Parameters already initialized and force_init=False. "
                "set_params call ignored.")
            return
        # flush unflushed fused updates so params not in the given dicts
        # keep their trained values (the partial set below overwrites
        # only the supplied entries)
        self._flush_fused()
        self._exec_group.set_params(arg_params, aux_params)
        self._params_dirty = True
        self.params_initialized = True
        if self._fused_step is not None:
            self._fused_stale = True

    # ---------------------------------------------------------- binding
    @staticmethod
    def _as_descs(shapes):
        if not shapes:
            return None
        return [s if isinstance(s, DataDesc) else DataDesc(s[0], s[1])
                for s in shapes]

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write", sharding=None):
        """Bind executors over the contexts (reference
        module/module.py:305-430 semantics). `sharding` (a
        `mxnet_tpu.sharding.ShardingPlan`) attaches/overrides the
        module's plan for this bind; explicit plan overrides are
        verified against the inferred parameter shapes BEFORE any
        trace — a non-dividing axis raises GraphVerifyError naming the
        parameter, the axis, and both sizes."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        if inputs_need_grad and not for_training:
            raise MXNetError("inputs_need_grad requires for_training")
        if sharding is not None:
            self._sharding_plan = sharding
            self._mesh_shape = dict(sharding.axis_sizes)
        if self._sharding_plan is not None:
            self._verify_sharding_plan(data_shapes, label_shapes)

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        self._data_shapes = self._as_descs(data_shapes)
        self._label_shapes = self._as_descs(label_shapes)

        shared_group = None
        if shared_module is not None:
            if not (shared_module.binded
                    and shared_module.params_initialized):
                raise MXNetError(
                    "shared_module must be bound and initialized")
            # modules that share executors mutate params through shared
            # NDArrays — incompatible with a fused step owning them.
            # MXNET_TPU_BUCKET_FUSED=1 keeps the fused step instead:
            # every bucket builds its own step and BucketingModule
            # hands the ONE canonical (params, states, auxs, t) to the
            # active bucket on switch (_adopt_fused), the analog of
            # the reference's per-bucket cached graphs sharing arrays.
            from .. import utils as _utils

            if not _utils.getenv("MXNET_TPU_BUCKET_FUSED"):
                shared_module._disable_fused(
                    "module is shared (bucketing); reverting to eager "
                    "updates")
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names,
        )

        if shared_module is not None:
            # adopt the sharing module's host-side param dicts wholesale
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            if shared_module.optimizer_initialized:
                self.borrow_optimizer(shared_module)
            return

        if self.params_initialized:
            # re-bind: push the existing values down to the executors
            self._exec_group.set_params(self._arg_params,
                                        self._aux_params)
            return

        # fresh bind: allocate the module-level master copies, shaped
        # like the executors' device arrays
        def alloc(names, blocks):
            return {
                name: nd.zeros(block[0].shape, dtype=block[0].dtype,
                               ctx=block[0].context)
                for name, block in zip(names, blocks)
            }

        self._arg_params = alloc(self._param_names,
                                 self._exec_group.param_arrays)
        self._aux_params = alloc(self._aux_names,
                                 self._exec_group.aux_arrays)

    def reshape(self, data_shapes, label_shapes=None):
        """(reference module/module.py:432)"""
        assert self.binded
        self._data_shapes = [
            x if isinstance(x, DataDesc) else DataDesc(x[0], x[1])
            for x in data_shapes
        ]
        if label_shapes is not None:
            self._label_shapes = [
                x if isinstance(x, DataDesc) else DataDesc(x[0], x[1])
                for x in label_shapes
            ]
        else:
            self._label_shapes = None
        self._exec_group.reshape(self._data_shapes, self._label_shapes)

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """(reference module/module.py:440-530)"""
        assert self.binded and self.params_initialized

        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        # re-initializing mid-training: preserve fused-step progress
        # before the old step is dropped
        if self._fused_step is not None:
            self._flush_fused()
            self._fused_step = None

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params,
            plan=self._sharding_plan)

        # normalize gradients by the GLOBAL batch (all devices, and all
        # workers under a synchronous distributed kvstore)
        global_batch = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            global_batch *= kvstore.num_workers
        elif kvstore and "tpu" in kvstore.type and kvstore.num_workers > 1:
            # fused multi-process data plane: each worker feeds a shard
            # of the global batch when the mesh has a process-spanning
            # 'data' axis; a pure-model mesh replicates the batch. ONE
            # decision shared with _build_fused_step so the gradient
            # normalization can't diverge from the actual batch scale.
            global_batch *= self._multiproc_mesh_plan()[1]
        rescale_grad = 1.0 / global_batch

        if isinstance(optimizer, str):
            # index->name map: the eager update path fakes one index per
            # (param, device) pair so per-param state is per-device
            names = self._exec_group.param_names
            ndev = 1 if update_on_kvstore else len(self._context)
            idx2name = {
                i * ndev + k: n
                for i, n in enumerate(names)
                for k in range(ndev)
            }
            settings = dict(optimizer_params)
            settings.setdefault("rescale_grad", rescale_grad)
            optimizer = opt.create(
                optimizer, sym=self.symbol, param_idx2name=idx2name,
                **settings
            )
        elif not isinstance(optimizer, opt.Optimizer):
            raise MXNetError("optimizer must be a name or an Optimizer")
        elif optimizer.rescale_grad != rescale_grad:
            self.logger.warning(
                "Optimizer created manually outside Module but "
                "rescale_grad is not normalized to 1.0/batch_size/"
                f"num_workers ({optimizer.rescale_grad} vs. "
                f"{rescale_grad}). Is this intended?")

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            # copy initialized local parameters to kvstore
            _initialize_kvstore(
                kvstore=kvstore,
                param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params,
                param_names=self._param_names,
                update_on_kvstore=update_on_kvstore,
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True

        self._build_fused_step()

        if (kvstore and "tpu" in kvstore.type
                and kvstore.num_workers > 1
                and self._fused_step is None):
            # eager fallback under kvstore('tpu'): push SUMS gradients
            # across workers regardless of the fused mesh plan, so the
            # normalization must include num_workers even when the plan
            # said replicated-batch (scale 1)
            expected = 1.0 / (self._exec_group.batch_size
                              * kvstore.num_workers)
            if self._optimizer.rescale_grad != expected:
                self.logger.warning(
                    "fused train step unavailable; the eager "
                    "kvstore('tpu') path sums gradients over %d "
                    "workers — adjusting rescale_grad %g -> %g",
                    kvstore.num_workers, self._optimizer.rescale_grad,
                    expected)
                self._optimizer.rescale_grad = expected

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _verify_sharding_plan(self, data_shapes, label_shapes):
        """Pre-trace sharding verification: infer every parameter's
        shape from the bind shapes and reject explicit plan overrides
        whose mesh-axis sizes do not divide the pinned dims
        (analysis.graph_verify.verify_sharding — the named-diagnostic
        alternative to a jax lowering error deep inside the first
        trace). Inference failures are left for the executor's own
        bind-time diagnostics."""
        from ..analysis import graph_verify as _gv

        known = {}
        for s in self._as_descs(data_shapes) or []:
            known[s.name] = tuple(s.shape)
        for s in self._as_descs(label_shapes) or []:
            known[s.name] = tuple(s.shape)
        try:
            arg_shapes, _, _ = self._symbol.infer_shape(**known)
            names = self._symbol.list_arguments()
        except Exception:
            return
        if arg_shapes is None:
            return
        shapes = {
            n: tuple(s) for n, s in zip(names, arg_shapes)
            if n in set(self._param_names) and s is not None
        }
        _gv.verify_sharding(self._sharding_plan, shapes)

    # ----------------------------------------------- fused train step
    def _multiproc_mesh_plan(self):
        """(use_model_mesh, batch_scale) for the multi-process fused
        data plane — the ONE place deciding whether mesh_shape is usable
        across processes and how many per-process batches make a global
        batch. init_optimizer (rescale_grad) and _build_fused_step
        (mesh + executor shapes) must agree on this or gradients get
        silently mis-normalized."""
        import math

        import jax

        from ..parallel.mesh import DATA_AXIS

        nproc = jax.process_count()
        if nproc <= 1:
            return (False, 1)
        ms = self._mesh_shape
        if ms:
            size = math.prod(ms.values())
            d = ms.get(DATA_AXIS, 1)
            if size == jax.device_count() and (
                    DATA_AXIS not in ms or d % nproc == 0):
                return (True, nproc if DATA_AXIS in ms else 1)
        # fallback (no/unusable mesh_shape): 1-D process-spanning
        # data mesh, every worker feeds a batch shard
        return (False, nproc)

    def _build_fused_step(self, carry_from=None):
        """Build the one-donated-jit train step when the configuration
        supports it; otherwise leave the eager executor-group path.

        Single context: plain fused step. Multiple contexts with
        KVStore('tpu'): ONE jit over a device mesh whose data axis spans
        the contexts — the executor-group's per-device executors collapse
        into GSPMD shardings and the gradient all-reduce happens inside
        the step (the north-star path of SURVEY.md §7 stage 7).
        """
        import jax

        from ..parallel.dp_step import FusedTrainStep, supports_fused

        self._fused_step = None
        self._fused_stale = False
        if (self._state_names or self.inputs_need_grad
                or not self.for_training
                or (self._monitor is not None and not getattr(
                    self._monitor, "device", False))):
            return
        if not supports_fused(self._optimizer):
            return
        # the fused step has write-update semantics; grad_req "add"
        # (gradient accumulation) or custom per-param reqs need the
        # eager executors
        if any(self._exec_group.grad_req.get(n) != "write"
               for n in self._param_names
               if n not in self._fixed_param_names):
            return
        nproc = jax.process_count()
        mesh = None
        if nproc > 1:
            # multi-process fused data plane: ONE mesh over the global
            # device set; each process feeds its local batch shard and
            # the gradient all-reduce runs inside the jit over DCN/ICI
            # (replaces the host-staged KVStore push/pull fallback,
            # which remains for non-fused configs)
            kv_type = self._kvstore.type if self._kvstore else ""
            if "tpu" not in kv_type and "dist" not in kv_type:
                return
            if "async" in kv_type:
                # dist_async is a parameter-server data plane by
                # definition — a barrier-synchronized in-jit all-reduce
                # would defeat its straggler tolerance
                return
            import numpy as np
            from jax.sharding import Mesh

            from ..parallel.mesh import make_mesh

            use_model_mesh, _scale = self._multiproc_mesh_plan()
            if use_model_mesh:
                # multi-host model parallelism: the SAME global mesh on
                # every process (make_mesh lays the data axis process-
                # major), so TP/SP/PP/EP shardings compose with cross-
                # host DP exactly as the reference's PlaceDevice +
                # dist kvstore compose (graph_executor.cc:242-318 +
                # kvstore_dist.h:35-51) — but as GSPMD collectives
                # instead of ZPush/ZPull.
                mesh = make_mesh(self._mesh_shape)
            else:
                if self._mesh_shape:
                    self.logger.warning(
                        "mesh_shape %s unusable across %d processes "
                        "(must cover all %d devices, with a 'data' axis "
                        "divisible by the process count when present); "
                        "falling back to a 1-D data mesh",
                        self._mesh_shape, nproc, jax.device_count())
                mesh = Mesh(np.asarray(jax.devices()), ("data",))
        elif self._mesh_shape:
            from ..parallel.mesh import make_mesh

            try:
                mesh = make_mesh(self._mesh_shape)
            except Exception as exc:
                # a mesh the process cannot build is the user's
                # parallelism silently gone: refuse, don't train on
                # one device
                raise MXNetError(
                    f"mesh_shape {self._mesh_shape} cannot be built "
                    f"on this process's devices: {exc}") from exc
        elif len(self._context) > 1:
            devs = [c.jax_device() for c in self._context]
            if len(set(devs)) != len(devs):
                raise MXNetError(
                    f"contexts {self._context} resolve to "
                    f"{len(set(devs))} distinct device(s) {devs}: each "
                    "context of a multi-device Module needs a device "
                    "of its own")
            kv_type = self._kvstore.type if self._kvstore else ""
            if "tpu" not in kv_type:
                return  # keep reference executor-group semantics
            import numpy as np
            from jax.sharding import Mesh

            if self._exec_group.batch_size % len(devs) != 0:
                # the executor group slices an uneven batch over the
                # same devices; only the fused mesh needs even shards
                return
            mesh = Mesh(np.asarray(devs), ("data",))
        param_specs, data_specs = self._collect_shardings(mesh)

        # ShardingPlan (mxnet_tpu.sharding): merge the rule layer into
        # the spec tables. Precedence: explicit plan overrides >
        # Symbol __sharding__ attrs > plan default rules. Inputs not
        # pinned elsewhere shard dim 0 over the plan's batch axes
        # ('data'+'fsdp' — fsdp ranks consume distinct rows).
        plan = self._sharding_plan
        if plan is not None and mesh is not None:
            plan.adopt_mesh(mesh)
            plan_specs = plan.resolve(
                {n: tuple(self._arg_params[n].shape)
                 for n in self._param_names})
            merged = dict(plan_specs)
            merged.update(param_specs)
            for n in plan.explicit_names & set(plan_specs):
                merged[n] = plan_specs[n]
            param_specs = merged
            for x in (self._data_shapes or []) + (
                    self._label_shapes or []):
                if x.name not in data_specs:
                    data_specs[x.name] = plan.input_spec(
                        x.name, ndim=len(x.shape))

        # dedicated executor bound with the GLOBAL batch shapes (the
        # exec-group executors hold per-device slices; under
        # multi-process each worker binds its LOCAL batch and the
        # global batch is scale x that, reference dist_sync semantics —
        # scale is 1 on a pure-model mesh, where every process feeds
        # the identical replicated batch). Per input: only inputs whose
        # dim 0 shards over the process-spanning 'data' axis (the
        # default, or an explicit spec naming it) have global dim0 =
        # scale x local; an input pinned off 'data' (e.g. a replicated
        # mask) keeps its local shape globally.
        from ..parallel.mesh import DATA_AXIS as _DATA

        scale = self._multiproc_mesh_plan()[1] if nproc > 1 else 1

        def input_scale(name):
            if scale == 1:
                return 1
            spec = data_specs.get(name)
            if spec is not None:
                dim0 = spec[0] if len(spec) else None
                axes = dim0 if isinstance(dim0, tuple) else (dim0,)
                if _DATA not in axes:
                    return 1
            return scale

        def up(shape, name):
            s = input_scale(name)
            return (shape[0] * s,) + tuple(shape[1:]) if s > 1 \
                else tuple(shape)

        shapes = {x.name: up(x.shape, x.name)
                  for x in self._data_shapes}
        if self._label_shapes:
            shapes.update(
                {x.name: up(x.shape, x.name)
                 for x in self._label_shapes})
        types = {x.name: x.dtype for x in self._data_shapes}
        if self._label_shapes:
            types.update({x.name: x.dtype for x in self._label_shapes})
        try:
            fexec = self._symbol.simple_bind(
                ctx=self._context[0], grad_req="write",
                type_dict=types, sharding=plan, **shapes)
        except Exception as exc:
            self.logger.warning("fused train step unavailable: %s", exc)
            return
        for n in self._fixed_param_names:
            fexec._grad_req[n] = "null"
        fexec.copy_params_from(self._arg_params, self._aux_params,
                               allow_extra_params=True)
        self._fused_step = FusedTrainStep(
            fexec, self._optimizer, self._param_names,
            label_names=self._label_names, mesh=mesh,
            compute_dtype=self._compute_dtype,
            param_specs=param_specs, data_specs=data_specs,
            batch_scale=scale, logger=self.logger, plan=plan,
        )
        # the fused step copied what it needs; drop the dedicated
        # executor's buffers so params/grads aren't resident three times
        fexec.release_arrays()
        if carry_from is not None:
            # carry only OPTIMIZER state: params/auxs were taken fresh
            # from _arg_params (callers sync those first), so carrying
            # the old step's possibly-stale arrays would undo
            # set_params/eager updates
            self._fused_step.states = dict(carry_from.states)
            self._fused_step._t = carry_from._t
        self._fused_dirty = False
        self._eager_seed_t = 0
        self._opt_state_bifurcated = False

    def _collect_shardings(self, mesh):
        """({param: spec}, {input: spec}) from Symbol `__sharding__`
        attrs + the data_shardings ctor arg, validated against the mesh
        axes. Unknown axes are dropped with a warning (the Symbol may
        carry annotations for a larger mesh than this run's)."""
        if mesh is None:
            return {}, {}
        from ..parallel.mesh import parse_partition_spec

        def valid(spec, name):
            used = []
            for dim in spec:
                for ax in (dim if isinstance(dim, tuple) else (dim,)):
                    if ax is not None:
                        used.append(ax)
            missing = [a for a in used if a not in mesh.axis_names]
            if missing:
                self.logger.warning(
                    "sharding for %r uses mesh axes %s not in mesh %s; "
                    "ignoring the annotation", name, missing,
                    dict(zip(mesh.axis_names, mesh.devices.shape)))
                return None
            return spec

        attrs = self._symbol.attr_dict()
        param_specs, data_specs = {}, {}
        for name in self._param_names:
            s = attrs.get(name, {}).get("__sharding__")
            if s is not None:
                spec = valid(parse_partition_spec(s), name)
                if spec is not None:
                    param_specs[name] = spec
        input_names = self._data_names + self._label_names
        for name in input_names:
            s = self._data_shardings.get(
                name, attrs.get(name, {}).get("__sharding__"))
            if s is not None:
                spec = valid(parse_partition_spec(s), name)
                if spec is not None:
                    data_specs[name] = spec
        return param_specs, data_specs

    def _disable_fused(self, reason=None):
        if self._fused_step is None:
            return
        if getattr(self, "_fused_surrendered", False):
            # a non-owner in fused bucketing: its arrays are stale (or
            # already donated by the owner's step) — drop the step
            # WITHOUT flushing; the owner carries the canonical state
            self._fused_step = None
            return
        if reason:
            self.logger.info("disabling fused train step: %s", reason)
        self._flush_fused()
        if self._fused_step._t:
            # hand the accumulated optimizer state (momentum, Adam
            # moments, ...) to whichever eager updater takes over;
            # Updater.set_states understands the fused format
            blob = self._fused_step.get_states()
            target = self._updater
            if target is None and self._kvstore is not None:
                target = getattr(self._kvstore, "_updater", None)
            if target is not None:
                try:
                    target.set_states(blob)
                except Exception as exc:
                    self.logger.warning(
                        "could not transfer fused optimizer state to "
                        "the eager updater: %s", exc)
        self._fused_step = None

    def _eager_updater(self):
        """The updater the eager update path drives (module-held, or
        the kvstore's server-side one)."""
        if self._updater is not None:
            return self._updater
        if self._kvstore is not None:
            return getattr(self._kvstore, "_updater", None)
        return None

    def _flush_fused(self):
        """Write fused-owned params/auxs back into the module + executor
        NDArrays so non-fused paths see current values. Uses copies:
        the live fused buffers get donated on the next step."""
        if self._fused_step is None or not self._fused_dirty:
            return
        if getattr(self, "_fused_surrendered", False):
            return  # stale/donated arrays: owner holds the real state
        params, auxs = self._fused_step.snapshot()
        for n, v in params.items():
            self._arg_params[n]._set_data(v)
        for n, v in auxs.items():
            self._aux_params[n]._set_data(v)
        self._exec_group.set_params(self._arg_params, self._aux_params)
        self._fused_dirty = False

    def _stage_for_fused(self, data_batch):
        """Convert a DataBatch into the fused step's {name: array} input,
        or None when the batch doesn't fit the fused signature."""
        import jax.numpy as jnp

        from .. import ndarray as _nd

        def val(arr):
            return arr._data if isinstance(arr, _nd.NDArray) \
                else jnp.asarray(arr)

        try:
            vals = {}
            for desc, arr in zip(self._data_shapes, data_batch.data):
                vals[desc.name] = val(arr)
            if self._label_shapes and data_batch.label:
                for desc, arr in zip(self._label_shapes, data_batch.label):
                    vals[desc.name] = val(arr)
        except Exception:
            return None
        if set(vals) != set(self._fused_step._data_names):
            return None
        mesh = self._fused_step._mesh
        if mesh is not None:
            scale = self._fused_step._batch_scale

            def dim0_axes(name):
                spec = self._fused_step._data_specs.get(name)
                if spec is None:
                    ax = self._fused_step._data_axis
                    return (ax,) if ax in mesh.axis_names else ()
                if len(spec) == 0 or spec[0] is None:
                    return ()
                return spec[0] if isinstance(spec[0], tuple) \
                    else (spec[0],)

            for k, v in vals.items():
                axes = dim0_axes(k)
                d = 1
                for a in axes:
                    d *= mesh.shape[a]
                # GLOBAL dim 0 is scale x local only for inputs whose
                # dim 0 shards over the process-spanning data axis
                # (matches _build_fused_step's input_scale)
                s = scale if self._fused_step._data_axis in axes or \
                    self._fused_step._data_specs.get(k) is None else 1
                if d > 1 and (v.ndim == 0 or (v.shape[0] * s) % d != 0):
                    # a partial batch can't shard evenly over the
                    # mesh; let the eager executors handle it
                    return None
        return vals

    def cast_compute(self, dtype):
        """Set the mixed-precision compute dtype (e.g. jnp.bfloat16):
        fp32 master weights, castcompute forward/backward. The analog of
        the reference's fp16 training path
        (tests/python/train/test_dtype.py)."""
        self._compute_dtype = dtype
        if self.optimizer_initialized:
            old = self._fused_step
            if self._params_dirty:
                self._sync_params_from_devices()
            self._build_fused_step(carry_from=old)

    def sync(self):
        """Block until all pending device work for the parameters is
        done (the analog of NDArray.wait_to_read on every param).
        Ends in a one-element value fetch: a value on the host is a
        fence whatever the backend's block_until_ready acknowledges."""
        import jax
        import numpy as np

        if self._fused_step is not None:
            self._fused_step.sync()
        elif self._exec_group is not None:
            for block in self._exec_group.param_arrays:
                for arr in block:
                    jax.block_until_ready(arr._data)
            if self._exec_group.param_arrays:
                leaf = self._exec_group.param_arrays[0][0]._data
                np.asarray(jax.device_get(leaf.ravel()[0]))

    def train_step_flops(self):
        """FLOPs of one fused train step per XLA cost analysis (0 when
        the fused path is inactive or not yet compiled)."""
        return self._fused_step.flops() if self._fused_step else 0.0

    def borrow_optimizer(self, shared_module):
        """(reference module/module.py:532)"""
        from .. import utils as _utils

        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True
        if (_utils.getenv("MXNET_TPU_BUCKET_FUSED")
                and shared_module._fused_step is not None):
            # fused bucketing: this bucket gets its OWN compiled step
            # (per-bucket shapes, like the reference's per-bucket
            # cached graphs) and immediately adopts the lender's
            # canonical training state
            self._build_fused_step()
            self._adopt_fused(shared_module)

    def _adopt_fused(self, other):
        """Take over the canonical fused training state (params,
        optimizer state, auxs, step count) and coherence flags from
        `other` — the bucket-switch handoff. The previous owner's
        arrays may be invalidated by this step's donation; switching
        back hands the fresh arrays over again."""
        src, dst = other._fused_step, self._fused_step
        if src is None or dst is None or src is dst:
            return
        dst.params = dict(src.params)
        dst.states = dict(src.states)
        dst.auxs = dict(src.auxs)
        dst._t = src._t
        self._fused_dirty = other._fused_dirty
        self._params_dirty = other._params_dirty
        self._fused_stale = other._fused_stale
        self._opt_state_bifurcated = other._opt_state_bifurcated
        self._eager_seed_t = other._eager_seed_t
        self._fused_surrendered = False
        # the previous owner's references go stale the moment this
        # module's step donates the arrays: bulk operations over all
        # buckets (install_monitor, save) must not flush them
        other._fused_surrendered = True
        other._opt_state_bifurcated = False

    def _refresh_fused_state(self):
        """Reload the fused step when params (and possibly optimizer
        state) changed outside it — an eager update or set_params made
        the fused copies stale."""
        if not self._fused_stale:
            return
        if self._params_dirty and not self._fused_dirty:
            self._exec_group.get_params(
                self._arg_params, self._aux_params)
            self._params_dirty = False
        self._fused_step.load_params(
            self._arg_params, self._aux_params)
        if self._opt_state_bifurcated:
            # fold the eager updater's optimizer state back so
            # momentum advanced by eager steps carries on
            target = self._eager_updater()
            if target is not None and target.states:
                try:
                    self._fused_step.set_states(target.get_states())
                except Exception as exc:
                    self.logger.warning(
                        "could not fold eager optimizer "
                        "state into the fused step: %s", exc)
            self._opt_state_bifurcated = False
        self._fused_stale = False

    def _slice_global_outputs(self, outs, b):
        """Multi-process fused outputs are replicated over the GLOBAL
        batch; when the batch is process-sharded (batch_scale > 1) this
        worker's rows are the contiguous local slice of b rows."""
        import jax as _jax
        import numpy as _np

        r = _jax.process_index()
        s = self._fused_step._batch_scale
        return [
            jnp_o[r * b:(r + 1) * b]
            if (s > 1 and jnp_o.ndim > 0 and jnp_o.shape[0] == b * s)
            else jnp_o
            for jnp_o in (
                _np.asarray(o.addressable_data(0)) if hasattr(
                    o, "addressable_data") else o
                for o in outs
            )
        ]

    # ------------------------------------------------------ computation
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        if (self._fused_step is not None and is_train
                and (self._monitor is None or getattr(
                    self._monitor, "device", False))):
            vals = self._stage_for_fused(data_batch)
            if vals is not None:
                self._refresh_fused_state()
                self._staged_batch = data_batch
                self._staged_vals = vals
                self._staged_outputs = None
                self._staged_backward = False
                return
        self._staged_batch = None
        self._staged_vals = None
        self._staged_outputs = None
        self._staged_backward = False
        self._flush_fused()
        self._exec_group.forward(data_batch, is_train)

    def _local_staged_rows(self, staged):
        """Dim 0 of any staged input whose leading axis shards over the
        process-spanning data axis — the per-process batch rows of THIS
        staged batch, which may be smaller than the bound batch size."""
        fs = self._fused_step
        for k, v in staged.items():
            if getattr(v, "ndim", 0) == 0:
                continue
            spec = fs._data_specs.get(k)
            if spec is None:
                return v.shape[0]
            if len(spec) and spec[0] is not None:
                axes = spec[0] if isinstance(spec[0], tuple) \
                    else (spec[0],)
                if fs._data_axis in axes:
                    return v.shape[0]
        return self._exec_group.batch_size

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._staged_vals is not None:
            if out_grads is None:
                # remember that gradients were requested: if the batch
                # later materializes eagerly (get_outputs before
                # update), the eager backward must run too
                self._staged_backward = True
                return
            # explicit head gradients (e.g. SequentialModule chaining):
            # the fused step cannot honor them — materialize the eager
            # forward for this batch and drop the staging
            self._materialize_staged(run_backward=False)
        self._flush_fused()
        self._exec_group.backward(out_grads=out_grads)

    def _materialize_staged(self, run_backward=None):
        """Replay the staged batch through the eager executors. When the
        user already called backward() on the staged batch, replay that
        too so grad arrays hold THIS batch's gradients."""
        if run_backward is None:
            run_backward = self._staged_backward
        batch = self._staged_batch
        self._staged_batch = None
        self._staged_vals = None
        self._staged_backward = False
        self._flush_fused()
        self._exec_group.forward(batch, True)
        if run_backward:
            self._exec_group.backward()

    def update(self):
        """(reference module/module.py:553-561)"""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized

        self._params_dirty = True
        if self._staged_vals is not None:
            staged = self._staged_vals
            outs = self._fused_step.step(staged)
            if self._fused_step._nproc > 1:
                # LOCAL batch rows: derived from the staged inputs, not
                # the bound batch size — _stage_for_fused admits partial
                # batches whose dim 0 still shards evenly
                outs = self._slice_global_outputs(
                    outs, self._local_staged_rows(staged))
            self._staged_outputs = [
                nd.NDArray(o, ctx=self._context[0]) for o in outs
            ]
            self._staged_batch = None
            self._staged_vals = None
            self._fused_dirty = True
            return
        if self._fused_step is not None and self._fused_step._t and \
                self._fused_step._t != self._eager_seed_t:
            # an eager update is about to run while the fused step holds
            # newer optimizer state (momentum/moments): seed the eager
            # updater from it so the two paths share ONE state lineage
            target = self._eager_updater()
            if target is not None:
                try:
                    target.set_states(self._fused_step.get_states())
                    self._eager_seed_t = self._fused_step._t
                except Exception as exc:
                    self.logger.warning(
                        "could not seed eager updater from fused "
                        "optimizer state: %s", exc)
        if self._update_on_kvstore:
            _update_params_on_kvstore(
                self._exec_group.param_arrays,
                self._exec_group.grad_arrays,
                self._kvstore,
            )
        else:
            _update_params(
                self._exec_group.param_arrays,
                self._exec_group.grad_arrays,
                updater=self._updater,
                num_device=len(self._context),
                kvstore=self._kvstore,
            )
        if self._fused_step is not None:
            # an eager update landed in the exec-group arrays; the
            # fused step must reload params AND optimizer state before
            # its next step
            self._fused_stale = True
            self._opt_state_bifurcated = True

    def run_steps(self, data_batch, k, stacked=False):
        """Advance k train steps (forward+backward+update each) in ONE
        device dispatch via the fused step's compiled loop
        (FusedTrainStep.run_steps); the last inner step's outputs are
        readable via get_outputs().

        stacked=False replays one resident batch k times (synthetic
        benchmarking); stacked=True expects each data/label array with
        a leading (k,) axis of per-step batches — the training-accurate
        form.

        TPU-first analog of driving the reference's async dependency
        engine many steps ahead of the host without a sync (SURVEY
        §2.2, src/engine/threaded_engine.cc): here the step loop itself
        is compiled (lax.scan), so one dispatch carries k optimizer
        updates and the per-dispatch host cost amortizes k-fold."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        if k < 1:
            raise ValueError("run_steps needs k >= 1")

        def eager_fallback():
            # no fused path (monitor installed, exotic binding) or a
            # batch the fused signature can't shard: k eager train
            # iterations, same semantics
            for i in range(k):
                if stacked:
                    b = type(data_batch)(
                        data=[d[i] for d in data_batch.data],
                        label=[l[i] for l in (data_batch.label or [])],
                    )
                else:
                    b = data_batch
                self.forward_backward(b)
                self.update()

        if self._fused_step is None or self._monitor is not None:
            return eager_fallback()

        if stacked:
            # per-step batches carry a leading (k,) axis; stage (and
            # shard-check) the LAST step's slice through the shared
            # gate, then rebuild the stacked dict from its names
            per_step = type(data_batch)(
                data=[d[-1] for d in data_batch.data],
                label=[l[-1] for l in (data_batch.label or [])],
            )
            probe = self._stage_for_fused(per_step)
            if probe is None:
                return eager_fallback()
            from .. import ndarray as _nd
            import jax.numpy as jnp

            def val(arr):
                return arr._data if isinstance(arr, _nd.NDArray) \
                    else jnp.asarray(arr)

            vals = {}
            for desc, arr in zip(self._data_shapes, data_batch.data):
                vals[desc.name] = val(arr)
            if self._label_shapes and data_batch.label:
                for desc, arr in zip(self._label_shapes,
                                     data_batch.label):
                    vals[desc.name] = val(arr)
            local_rows = self._local_staged_rows(probe)
        else:
            vals = self._stage_for_fused(data_batch)
            if vals is None:
                return eager_fallback()
            local_rows = self._local_staged_rows(vals)

        self._refresh_fused_state()
        self._params_dirty = True
        outs = self._fused_step.run_steps(vals, k, stacked=stacked)
        if self._fused_step._nproc > 1:
            outs = self._slice_global_outputs(outs, local_rows)
        self._staged_outputs = [
            nd.NDArray(o, ctx=self._context[0]) for o in outs
        ]
        self._staged_batch = None
        self._staged_vals = None
        self._fused_dirty = True

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._staged_outputs is not None:
            outs = self._staged_outputs
            return outs if merge_multi_context else [[o] for o in outs]
        if self._staged_batch is not None:
            # forward() staged but update() hasn't run: materialize the
            # eager forward (params are still current) and fall back to
            # the eager path for the rest of this batch's lifecycle
            self._materialize_staged()
        return self._exec_group.get_outputs(
            merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized \
            and self.inputs_need_grad
        return self._exec_group.get_input_grads(
            merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._staged_outputs is not None:
            _metric.update_auto(eval_metric, labels, self._staged_outputs)
            return
        if self._staged_batch is not None:
            # metric asked for before update(): materialize the eager
            # forward so the metric reflects THIS batch, not stale
            # executor outputs
            self._materialize_staged()
        self._exec_group.update_metric(eval_metric, labels)

    def _step_fence(self):
        """A device array that completes no earlier than the most
        recently dispatched step — what fit's dispatch-ahead window
        waits on to bound in-flight work. None when nothing usable is
        staged (the window then simply stays empty)."""
        if self._staged_outputs:
            return self._staged_outputs[0]._data
        if self._exec_group is not None and self._exec_group.execs:
            outs = self._exec_group.execs[0].outputs
            if outs:
                return outs[0]._data
        return None

    def _sync_params_from_devices(self):
        """(reference module/module.py:587)"""
        if self._fused_step is not None and self._fused_dirty:
            self._flush_fused()
        else:
            # eager updates live in the executor-group arrays
            self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        """(reference module/module.py:597)"""
        assert self.optimizer_initialized
        if self._fused_step is not None:
            with open(fname, "wb") as fout:
                fout.write(self._fused_step.get_states())
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """(reference module/module.py:610)"""
        assert self.optimizer_initialized
        if self._fused_step is not None:
            with open(fname, "rb") as fin:
                self._fused_step.set_states(fin.read())
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())

    def install_monitor(self, mon):
        assert self.binded
        self._monitor = mon
        if getattr(mon, "device", False):
            # device-mode monitor (Monitor(device=True)): its stats
            # come from the numerics sentinel row computed INSIDE the
            # fused step, so the fused path stays alive — no eager
            # per-node fallback, no per-tensor host syncs
            install_module = getattr(mon, "install_module", None)
            if install_module is not None:
                install_module(self)
            for exe in self._exec_group.execs:
                mon.install(exe)
            return
        self._disable_fused("monitor installed (eager per-node execution)")
        for exe in self._exec_group.execs:
            mon.install(exe)

    def _ensure_sentinel(self):
        """Enable the numerics sentinel on the fused step (idempotent).
        Returns the active SentinelSpec, or None when this module has
        no fused train path for the sentinel row to live in."""
        fs = getattr(self, "_fused_step", None)
        if fs is None:
            return None
        if fs._sentinel is not None:
            return fs._sentinel
        from ..numerics.sentinel import SentinelSpec

        spec = SentinelSpec(fs._trainable)
        fs.enable_sentinel(spec)
        return spec
