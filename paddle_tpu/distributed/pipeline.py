"""Pipeline parallelism.

Reference parity: PipelineOptimizer (fleet/meta_optimizers/pipeline_optimizer.py:25)
splits the program into device-guard sections; PipelineTrainer + SectionWorker run
micro-batches with the 1F1B schedule (framework/section_worker.cc:98-141, schedule
comment :129); P2P via send_v2/recv_v2 ops.

TPU-native design: the model is a list of stage Layers; the whole pipeline is ONE
shard_map over the 'pp' mesh axis. Every rank holds its stage's params; activations
move between ranks with ppermute each tick. The schedule is the classic pipelined loop
(n_micro + n_stages - 1 ticks): tick t gives rank r micro-batch (t - r) — i.e. GPipe
filling/draining expressed as a lax.fori_loop; XLA overlaps the ppermute with compute.
Gradient = jax.grad through the whole scanned schedule (no hand-written 1F1B backward —
autodiff produces the reverse schedule mechanically).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import flags as _flags
from ..core.tape import global_tape
from ..core.tensor import Tensor
from .spmd import _pvary as _vary   # the ONE device-varying carry helper

#: The stage-boundary transfer edge (ISSUE 13; docs/ANALYSIS.md
#: "Declaring a transfer edge"): what one pipeline rank's ppermute hands
#: the next rank every tick. The static auditor
#: (analysis/handoff_schema.py) AST-extracts this literal and pins its
#: fingerprint in tests/handoff_baseline.json; PipelineTrainer validates
#: its stage activation against the same declaration at build time
#: (``mb`` binds to the micro-batch rows, ``...`` covers the stage's
#: feature dims, ``$act`` the activation dtype). ROADMAP 3's MPMD
#: stage-program abstraction types its transfer edges with exactly this
#: payload form.
HANDOFF_SCHEMA = {
    "edge": "pipeline_stage",
    "producer": ("paddle_tpu/distributed/pipeline.py::"
                 "PipelineTrainer._pipelined"),
    "consumer": ("paddle_tpu/distributed/pipeline.py::"
                 "PipelineTrainer.train_step"),
    "runtime_checked": True,
    "doc": "one micro-batch of stage activations, carried rank->rank by "
           "the ppermute ring each schedule tick",
    "payload": {
        "activation": {"shape": ("mb", "..."), "dtype": "$act",
                       "layout": "[micro_batch, *stage_features]"},
    },
}


def _pure_call(layer, params, *args):
    """Call `layer` as a pure function of a params dict (name -> array)."""
    from ..core.functional import functional_state

    with functional_state(layer, params), global_tape().pause():
        out = layer(*[Tensor(a) if not isinstance(a, Tensor) else a for a in args])
        return out._data if isinstance(out, Tensor) else out


class PipelineStage:
    """One stage = a pure fn(params, x) -> y derived from a Layer."""

    def __init__(self, layer):
        self.layer = layer

    def pure(self, params, x):
        return _pure_call(self.layer, params, x)


def _stack_stage_params(stages):
    """Stack per-stage param pytrees along a leading 'pp' axis (stages must be
    structurally identical, like transformer blocks)."""
    layers = [getattr(s, "layer", s) for s in stages]
    names = [n for n, _ in layers[0].named_parameters()]
    stacked = {}
    for n in names:
        arrs = [dict(l.named_parameters())[n]._data for l in layers]
        stacked[n] = jnp.stack(arrs, axis=0)
    return stacked


class Pipeline:
    """1F1B/GPipe pipeline over the 'pp' mesh axis (homogeneous stages).

    loss_head(params_head, y, label) -> scalar runs on the last rank.
    """

    def __init__(self, stages, mesh, axis_name="pp", n_micro=None):
        assert len(stages) == mesh.shape[axis_name], "one stage per pp rank"
        self.stages = [PipelineStage(s) for s in stages]
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_stages = len(stages)
        self.n_micro = n_micro or self.n_stages
        self.stage_fn = self.stages[0].pure  # homogeneous structure

    def forward_fn(self):
        """Returns pure fn(stacked_params, x_micro[b...]) -> y (final stage output),
        to be wrapped in shard_map by the caller or used via run()."""
        ax = self.axis_name
        n_stage = self.n_stages
        n_micro = self.n_micro
        stage_fn = self.stage_fn

        def spmd(params_sharded, x_all):
            # params_sharded: leading pp dim is the local shard (size 1) -> strip it
            # x_all: [n_micro, mb, ...] — replicated input micro-batches
            params_my = {k: v[0] for k, v in params_sharded.items()}
            r = jax.lax.axis_index(ax)
            n_ticks = n_micro + n_stage - 1
            y_shape = x_all.shape[1:]

            # mark carry inits as device-varying over 'pp' (the module-
            # level _vary: shard_map vma typing)
            buf = _vary(jnp.zeros_like(x_all[0]), ax)  # rank-held activation
            outs = _vary(jnp.zeros((n_micro,) + y_shape, x_all.dtype), ax)
            perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

            def tick(t, carry):
                buf, outs = carry
                mb_idx = t - r  # micro-batch this rank works on at tick t
                active = (mb_idx >= 0) & (mb_idx < n_micro)
                # rank 0 ingests a fresh micro-batch; others use what arrived
                x_in = jnp.where(
                    r == 0,
                    x_all[jnp.clip(t, 0, n_micro - 1)],
                    buf,
                )
                y = stage_fn(params_my, x_in)
                y = jnp.where(active, y, jnp.zeros_like(y))
                # last rank records its finished micro-batch
                outs = jnp.where(
                    (r == n_stage - 1) & active,
                    outs.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(y),
                    outs,
                )
                # send activation to next rank
                buf_next = jax.lax.ppermute(y, ax, perm)
                return buf_next, outs

            _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
            # only the last rank recorded nonzero outputs -> psum replicates them
            return jax.lax.psum(outs, ax)

        return spmd

    def run(self, x):
        """Forward the full batch through the pipeline; returns final-stage outputs."""
        ax = self.axis_name
        params = _stack_stage_params(self.stages)
        x = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        mb = x.shape[0] // self.n_micro
        x_micro = x.reshape((self.n_micro, mb) + x.shape[1:])
        spmd = self.forward_fn()
        param_specs = {k: P(ax) for k in params}
        mapped = jax.shard_map(spmd, mesh=self.mesh,
                               in_specs=(param_specs, P()), out_specs=P())
        outs = mapped(params, x_micro)
        return Tensor(outs.reshape((self.n_micro * mb,) + outs.shape[2:]))


# ---------------------------------------------------------------------------
# Pipeline *training* — fwd + bwd + optimizer across stages
# ---------------------------------------------------------------------------

class PipelineTrainer:
    """Pipeline-parallel TRAINING over a pp(×dp) mesh — one jitted step.

    Reference parity: PipelineTrainer + SectionWorker's micro-batch schedule
    (framework/section_worker.cc:98-141) and PipelineOptimizer's program split
    (fleet/meta_optimizers/pipeline_optimizer.py:25). There, each device runs a
    program section and grads flow stage-to-stage via send_v2/recv_v2.

    TPU-native design (GSPMD-style "pipelining as collective permute"): the model
    is (pre, stages, post_loss) — embedding, N structurally identical stage
    layers, and a head+loss layer. Stage params are STACKED on a leading axis
    sharded over 'pp'; the GPipe fill/drain schedule (n_micro + n_stages - 1
    ticks, rank r works micro-batch t - r at tick t) is a lax.scan whose
    activations move between ranks with ppermute inside a shard_map that is
    manual over 'pp' and automatic over 'dp' (XLA inserts the dp grad psum).
    The backward schedule is autodiff's reversal of the forward scan — a drain/
    fill mirror, mechanically correct without hand-written 1F1B send/recv.

    Memory profile (honest note): reverse-mode through the scanned schedule
    retains O(n_ticks) per-tick residuals — the GPipe profile, not true 1F1B's
    O(n_stages). schedule_mode='1F1B' reclaims that headroom the TPU way:
    jax.checkpoint on each stage tick drops intra-stage residuals and recomputes
    them in the backward sweep, bounding live memory to the scan carries
    (one activation per tick) — the same peak-memory class 1F1B targets.
    schedule_mode='F-then-B' keeps all residuals (fastest, most memory).

    `pre` and `post_loss` params are replicated over pp (every rank computes
    them; only rank 0's / the psum'd last-rank path carries gradients — XLA
    dead-code-eliminates the rest).
    """

    def __init__(self, pre, stages, post_loss, optimizer, mesh=None,
                 pp_axis="pp", dp_axis="dp", n_micro=None,
                 schedule_mode="1F1B", donate=True, stage_param_specs=None,
                 stage_meshes=None, compress=None):
        """stage_param_specs: optional {stage_param_name: PartitionSpec}
        (collect_spmd_specs of one stage) adding a TENSOR-PARALLEL axis under
        the pipeline: stacked stage params shard P('pp', *spec) and XLA's
        sharding propagation inserts the mp collectives inside each stage
        tick (the shard_map is manual over pp only; dp/mp stay automatic) —
        3-axis pp x dp x mp hybrid parallelism.

        stage_meshes / compress apply only under FLAGS_mpmd
        (distributed/stage.py): an explicit per-stage mesh list (unequal
        device counts allowed) and int8 edge quantization (compress=8) for
        the activation edges. With the flag unset both must stay None —
        passing them is a config error, not a silent no-op."""
        from .mesh import get_mesh

        from .split import collect_spmd_specs

        self.mesh = mesh or get_mesh()
        assert pp_axis in self.mesh.axis_names, f"mesh needs a '{pp_axis}' axis"
        self.stage_param_specs = dict(stage_param_specs or {})
        if self.stage_param_specs:
            known = {n for n, _ in stages[0].named_parameters()}
            unknown = sorted(set(self.stage_param_specs) - known)
            if unknown:
                raise ValueError(
                    f"stage_param_specs names no stage-0 params: {unknown} "
                    "— pass collect_spmd_specs(stages[0]) (stage-local "
                    "names), not full-model paths")
        # pre/post tensor-parallel specs (vocab-parallel embedding, split lm
        # head — the largest GPT tensors) apply automatically when present
        self.pre_param_specs = collect_spmd_specs(pre)
        self.post_param_specs = collect_spmd_specs(post_loss)
        self.pre = pre
        self.stage_layers = list(stages)
        self.post_loss = post_loss
        self.optimizer = optimizer
        self.pp_axis = pp_axis
        self.dp_axis = dp_axis if dp_axis in self.mesh.axis_names else None
        self.n_stages = self.mesh.shape[pp_axis]
        assert len(self.stage_layers) == self.n_stages, \
            f"{len(self.stage_layers)} stages for pp={self.n_stages}"
        self.n_micro = n_micro or self.n_stages
        self.schedule_mode = schedule_mode
        self.donate = donate
        self._compiled = None
        self._edge_checked = False

        # stage params must be uniformly trainable across stages (they are one
        # stacked array) — a per-stage freeze cannot be expressed, so reject it
        stacked = _stack_stage_params(self.stage_layers)
        stage0_named = dict(self.stage_layers[0].named_parameters())
        for i, s in enumerate(self.stage_layers[1:], start=1):
            for n, p in s.named_parameters():
                if getattr(p, "trainable", True) != getattr(
                        stage0_named[n], "trainable", True):
                    raise ValueError(
                        f"stage {i} param '{n}' trainable flag differs from "
                        "stage 0; stacked pipeline stages must be uniformly "
                        "trainable — freeze the same params on every stage")
        self.params, self.frozen = {}, {}
        for n, p in pre.named_parameters():
            dst = self.params if getattr(p, "trainable", True) else self.frozen
            dst["pre::" + n] = p._data
        for n, v in stacked.items():
            trainable = getattr(stage0_named[n], "trainable", True)
            (self.params if trainable else self.frozen)["stage::" + n] = v
        for n, p in post_loss.named_parameters():
            dst = self.params if getattr(p, "trainable", True) else self.frozen
            dst["post::" + n] = p._data
        self.opt_state = optimizer.functional_init(self.params)
        self._place_state()
        # MPMD stage-program runtime (distributed/stage.py): the flag is
        # consumed HERE — the armed trainer builds per-stage programs and
        # typed edges over the state placed above, so a post-construction
        # toggle raises (_mpmd_active) instead of silently switching
        # schedulers mid-run. Only the armed path imports the module.
        self._mpmd = bool(_flags.get_flag("mpmd", False))
        self._mpmd_runner = None
        if not self._mpmd and (stage_meshes is not None
                               or compress is not None):
            raise ValueError(
                "stage_meshes/compress are MPMD edge options "
                "(distributed/stage.py) — set FLAGS_mpmd before "
                "constructing the trainer")
        if self._mpmd:
            from . import stage as _stage_mod

            self._mpmd_runner = _stage_mod.MpmdPipelineRunner(
                self, stage_meshes=stage_meshes, compress=compress)

    def _mpmd_active(self):
        """FLAGS_mpmd was consumed at construction (the stage programs
        and edges are built then); a post-construction toggle is loud
        instead of silently swapping schedulers. One get_flag + compare
        when disarmed."""
        m = bool(_flags.get_flag("mpmd", False))
        if m != self._mpmd:
            raise RuntimeError(
                "FLAGS_mpmd changed after this PipelineTrainer was "
                "constructed; the stage programs and transfer edges are "
                "built at __init__ — build a new PipelineTrainer under "
                "the new flag value")
        return self._mpmd

    def numerics_fetch(self):
        """Numerics-telescope drain hook (testing/parity.py lockstep
        harness). The pipeline step doesn't thread the telescope — same
        carve-out as localsgd/DGC — so there is never anything to
        fetch."""
        return None

    # -- sharding placement ----------------------------------------------------
    def _sharding_for(self, name):
        grp, local = name.split("::", 1)
        if grp == "stage":
            spec = self.stage_param_specs.get(local)
            if spec is not None:
                # stacked stage param: leading pp dim + the stage-local
                # tensor-parallel spec on the remaining dims
                return NamedSharding(self.mesh, P(self.pp_axis, *spec))
            return NamedSharding(self.mesh, P(self.pp_axis))
        spec = (self.pre_param_specs if grp == "pre"
                else self.post_param_specs).get(local)
        if spec is not None and all(
                ax is None or ax in self.mesh.axis_names
                for d in spec for ax in
                ((d,) if not isinstance(d, tuple) else d)):
            return NamedSharding(self.mesh, P(*spec))
        return NamedSharding(self.mesh, P())

    def _place_state(self):
        from .spmd import owned_device_put

        self.p_shardings = {k: self._sharding_for(k) for k in self.params}
        self.params = {k: owned_device_put(v, self.p_shardings[k])
                       for k, v in self.params.items()}
        self.f_shardings = {k: self._sharding_for(k) for k in self.frozen}
        self.frozen = {k: jax.device_put(v, self.f_shardings[k])
                       for k, v in self.frozen.items()}
        self.s_shardings, new_state = {}, {}
        for pname, st in self.opt_state.items():
            if pname == "__step__":
                self.s_shardings[pname] = NamedSharding(self.mesh, P())
                new_state[pname] = owned_device_put(st, self.s_shardings[pname])
                continue
            sub_sh, sub = {}, {}
            for k, v in st.items():
                sh = (self._sharding_for(pname)
                      if hasattr(v, "ndim") and v.ndim > 0
                      else NamedSharding(self.mesh, P()))
                sub_sh[k] = sh
                sub[k] = owned_device_put(v, sh)
            self.s_shardings[pname] = sub_sh
            new_state[pname] = sub
        self.opt_state = new_state

    # -- the scheduled pipeline forward ---------------------------------------
    def _pipelined(self, stage_params, h_micro):
        """[n_micro, mb, ...] -> final-stage outputs [n_micro, mb, ...]."""
        ax = self.pp_axis
        n_stage, n_micro = self.n_stages, self.n_micro
        stage0 = self.stage_layers[0]
        base_fn = functools.partial(_pure_call, stage0)
        fn = jax.checkpoint(base_fn) if self.schedule_mode == "1F1B" else base_fn

        def spmd(params_sh, x_all):
            params_my = {k: v[0] for k, v in params_sh.items()}
            r = jax.lax.axis_index(ax)
            n_ticks = n_micro + n_stage - 1
            perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            buf0 = _vary(jnp.zeros_like(x_all[0]), ax)

            def tick(buf, t):
                mb_idx = t - r
                active = (mb_idx >= 0) & (mb_idx < n_micro)
                x_in = jnp.where(r == 0, x_all[jnp.clip(t, 0, n_micro - 1)], buf)
                y = fn(params_my, x_in)
                y = jnp.where(active, y, jnp.zeros_like(y))
                y_out = jnp.where(r == n_stage - 1, y, jnp.zeros_like(y))
                return jax.lax.ppermute(y, ax, perm), y_out

            _, ys = jax.lax.scan(tick, buf0, jnp.arange(n_ticks))
            # last rank finishes micro-batch m at tick m + n_stage - 1
            outs = ys[n_stage - 1:n_stage - 1 + n_micro]
            return jax.lax.psum(outs, ax)  # replicate from the last rank

        specs = {k: P(ax) for k in stage_params}
        mapped = jax.shard_map(spmd, mesh=self.mesh, in_specs=(specs, P()),
                               out_specs=P(), axis_names={ax})
        return mapped(stage_params, h_micro)

    # -- jitted train step ------------------------------------------------------
    def _build(self):
        pre, post = self.pre, self.post_loss

        def split_tree(flat, frozen):
            t = {"pre": {}, "stage": {}, "post": {}}
            for k, v in {**frozen, **flat}.items():
                grp, name = k.split("::", 1)
                t[grp][name] = v
            return t

        def step(params, opt_state, frozen, lr, x_micro, y_micro):
            def loss_fn(flat):
                t = split_tree(flat, frozen)
                h = jax.vmap(lambda xi: _pure_call(pre, t["pre"], xi))(x_micro)
                outs = self._pipelined(t["stage"], h)
                losses = jax.vmap(
                    lambda oi, yi: _pure_call(post, t["post"], oi, yi)
                )(outs, y_micro)
                return jnp.mean(losses.astype(jnp.float32))

            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_state = self.optimizer.functional_apply(
                params, grads, opt_state, lr=lr)
            return loss, new_params, new_state

        repl = NamedSharding(self.mesh, P())
        batch_sh = NamedSharding(
            self.mesh, P(None, self.dp_axis) if self.dp_axis else P())
        donate = (0, 1) if self.donate else ()
        return jax.jit(
            step,
            in_shardings=(self.p_shardings, dict(self.s_shardings),
                          self.f_shardings, repl, batch_sh, batch_sh),
            out_shardings=(repl, self.p_shardings, dict(self.s_shardings)),
            donate_argnums=donate,
        )

    def train_step(self, x, y):
        """x, y: full batch [B, ...]; B must divide by n_micro (and dp on the
        micro-batch dim). Returns the mean loss over all micro-batches."""
        x = x._data if isinstance(x, Tensor) else jnp.asarray(np.asarray(x))
        y = y._data if isinstance(y, Tensor) else jnp.asarray(np.asarray(y))
        assert x.shape[0] % self.n_micro == 0, \
            f"batch {x.shape[0]} not divisible by n_micro={self.n_micro}"
        mb = x.shape[0] // self.n_micro
        x_micro = x.reshape((self.n_micro, mb) + x.shape[1:])
        y_micro = y.reshape((self.n_micro, mb) + y.shape[1:])
        if not self._edge_checked:
            self._validate_stage_edge(x_micro)
        if self._mpmd_active():
            loss = self._mpmd_runner.train_step(x_micro, y_micro)
            self.optimizer._step_count += 1
            return Tensor(loss)
        if self._compiled is None:
            self._compiled = self._build()
        lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
        loss, self.params, self.opt_state = self._compiled(
            self.params, self.opt_state, self.frozen, lr, x_micro, y_micro)
        self.optimizer._step_count += 1
        return Tensor(loss)

    def _validate_stage_edge(self, x_micro):
        """Typed transfer edge (ISSUE 13): shape-infer one micro-batch
        through `pre` (eval_shape — nothing executes) and validate the
        activation the ppermute ring will carry against HANDOFF_SCHEMA —
        the same declaration the static auditor extracts and baselines.
        Runs once per trainer; raises HandoffMismatch naming the leaf."""
        from ..analysis import handoff_schema as _hs

        pre_params = {k.split("::", 1)[1]: v
                      for k, v in {**self.frozen, **self.params}.items()
                      if k.startswith("pre::")}
        act = jax.eval_shape(
            lambda p, xi: _pure_call(self.pre, p, xi), pre_params,
            jax.ShapeDtypeStruct(tuple(x_micro.shape[1:]), x_micro.dtype))
        # "$act" binds to the STAGES' compute dtype (their first floating
        # param), not to the payload's own dtype — the check must be able
        # to fail when `pre` hands the ring an activation the stacked
        # stage programs do not compute in
        stage_dt = next(
            (str(v.dtype) for k, v in {**self.params, **self.frozen}.items()
             if k.startswith("stage::")
             and jnp.issubdtype(v.dtype, jnp.floating)), str(act.dtype))
        _hs.validate(HANDOFF_SCHEMA, {"activation": act},
                     dims={"mb": int(x_micro.shape[1])},
                     dtypes={"act": stage_dt})
        self._edge_checked = True

    def sync_to_layer(self):
        """Write trained params back into pre/stages/post Layer tensors.

        Copies (never aliases) the trainer's arrays: the jitted step donates
        self.params, so handing those buffers to the Layer would let the next
        train_step invalidate the Layer's eager tensors."""
        pre_named = dict(self.pre.named_parameters())
        post_named = dict(self.post_loss.named_parameters())
        stage_named = [dict(s.named_parameters()) for s in self.stage_layers]
        for k, v in self.params.items():
            grp, name = k.split("::", 1)
            if grp == "pre":
                pre_named[name]._data = jnp.asarray(jax.device_get(v))
            elif grp == "post":
                post_named[name]._data = jnp.asarray(jax.device_get(v))
            else:
                host = jax.device_get(v)
                for i, named in enumerate(stage_named):
                    named[name]._data = jnp.asarray(host[i])

    # -- checkpoint / resume ---------------------------------------------------
    def state_dict(self):
        """Host-side checkpoint of the pipeline train state (stacked stage
        params + pre/post params + optimizer moments + step counters + LR
        scheduler); restore with set_state_dict for bit-exact resume."""
        from .spmd import gather_train_state

        return gather_train_state(self.params, self.opt_state,
                                  self.optimizer)

    def set_state_dict(self, state):
        from .spmd import restore_train_state

        self.params, self.opt_state = restore_train_state(
            state, self.p_shardings, self.s_shardings, self.optimizer)
