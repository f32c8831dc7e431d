"""The decode-model interface: what a model must provide to be SERVED.

The continuous-batching ``ServingEngine`` (inference/serving.py), the
front-door ``Router`` (serving/router.py), and the prefill/decode
``DisaggregatedPool`` (serving/disagg.py) are model-agnostic: they drive
any model through the :class:`DecodeModel` adapter protocol below instead
of importing a model module's privates. A model family registers ONE
adapter (``register_decode_model``); the serving tier resolves it by name
or by inspecting the model instance (``resolve``).

The protocol (docs/SERVING.md for the full contract):

``check_config(cfg)``
    Reject configs the decode programs cannot serve (MoE, megatron-
    training layouts, ...). Raises ``ValueError``.
``compute_dtype(dtype)``
    Map a user dtype string to the decode compute dtype (``None`` = f32).
``extract_params(model, who)``
    Name-addressed param snapshot -> ``(params, aux)``. ``params`` is a
    flat ``{name: jax array}`` dict; ``aux`` is adapter-opaque state
    threaded back into :meth:`decode_fns` (e.g. untied-head flags).
``decode_fns(cfg, aux, cache_dtype=None, tp_axis=None, tp_size=1)``
    The pure-jnp decode math: ``(fwd, logits_of, cache_init)`` with

    - ``cache_init(b, T, dt) -> (kc, vc)`` — the KV-cache pytree pair.
      Each of kc/vc is one "cache side": a plain array (leading axes
      ``[L, b, KVh, T, ...]``) or a (values, scales) tuple for quantized
      caches. Row 0..b-1 is one slot; the pair for ``b=1`` is the unit of
      PREFILL->DECODE HANDOFF (``ServingEngine.admit_prefilled``) — any
      engine built from the same adapter+config accepts another's rows.
    - ``fwd(params, tok_ids [B, t], pos, kc, vc) -> (x, kc, vc)`` — run
      the stack writing K/V at column(s) ``pos`` (scalar or per-row [B]).
    - ``logits_of(params, x_last) -> logits`` — project hidden states to
      vocab logits.
``tp_setup(tp_mesh, cfg, params)``
    Tensor-parallel serving setup -> ``(tp_axis, tp_size, params,
    param_specs)``; raise if the config cannot shard.
``tp_wrap(run, tp_mesh, tp_specs, n_extra_in, out_specs, in_specs=None,
  donate=())``
    jit(shard_map(run)) for the tp programs.
``cache_spec(cfg)``
    Machine-readable description of the cache pytree (layout string,
    axis names, quantized or not) — the handoff contract in data form.
    Its ``"leaves"`` describe the pair as a tree of STATE KINDS, one dict
    a leaf: ``path`` (from the pair's root: ``(0,)`` is the first half;
    a dict key or a tuple index a step; a path covers every leaf under
    it), ``kind`` (``"kv"``: grows with the context, written at ``pos``,
    a slot's junk past its request's columns is never seen;
    ``"recurrent"`` and ``"conv"``: a fixed size whatever the context,
    replaced every step), ``slot_axis`` and ``layers``. A ``kv`` leaf need
    not be keys or values of heads: models/axk1.py's is one latent row
    ``[L, B, T, W]`` for all heads. The engine's
    admission, hand-off rows, row copies and byte counts go by it
    (:func:`state_leaves`, :func:`slot_axes`, :func:`bytes_by_kind`,
    :func:`check_row`). A spec without ``"leaves"`` is a K/V pair with
    slots on axis 1. An adapter that declares a fixed-size kind is handed
    ``valid_len=`` by every whole-sequence call (docs/SERVING.md "State
    kinds").
``step_counts``
    Optional attribute: names of int32 counts a decode step returns
    beside its tokens. ``fwd(..., counts=True)`` then returns a fourth
    value, an int32 vector of them; the engine reads it with the step's
    tokens and sums it into ``stats()``.
``kv_read_tile(cfg, side, dtype, tp_size=1)``
    Optional: the width, in cache columns, of the tiles a one-token
    decode step with per-row positions reads each row in, up to the
    row's position and no further; None (the default) where it reads
    every column. ``side`` is one cache side as the same ``decode_fns``
    call's ``cache_init`` makes it (a shard's heads under tensor
    parallelism), ``dtype`` what the step computes in. The engine's
    ``kv_tiles_read`` / ``kv_tiles_held`` counts follow it.
``kv_tiles_written(cfg, side, dtype, tp_size=1)``
    Optional, same arguments: how many of those tiles such a step's
    attention writes back for each row, the step's new column stored into
    them (all layers, keys and values); 0 (the default) where the step
    stores by a call or a select of its own. The engine's
    ``kv_tiles_written`` count is rows times it.
``not_served``
    Optional attribute: ``{option: reason}`` of the engines beyond the
    dense one this adapter's programs do NOT serve: ``"paged_kv"``,
    ``"draft_model"``, ``"tp_mesh"``, ``"lora"``, ``"cache_dtype"``. The
    engine refuses each by the adapter's name and reason where it is
    asked for. What decides is this declaration, not the kinds of the
    cache's leaves: a latent cache has `kv` leaves only and still no
    pages of K/V heads for a paged pool.
``lora_init(cfg, n_slots, rank, dtype=None)`` / ``lora_pack(cfg,
  exported, rank)``
    Optional multi-LoRA batched decode (FLAGS_paged_kv engines): the
    stacked adapter pytree (slot 0 all-zero = base) and the packing of
    one exported adapter into a slot. ``fwd`` grows ``lora=`` /
    ``adapter_ids=`` kwargs applying the per-row low-rank delta.
``matches(model)``
    True when this adapter serves ``model`` (used by :func:`resolve`).

Exact-parity bar: an engine serving a model THROUGH its adapter must be
byte-identical to one calling the model's decode helpers directly — the
adapter delegates, it never re-implements math.
"""
import importlib

__all__ = ["DecodeModel", "register_decode_model", "get_decode_model",
           "registered_decode_models", "resolve", "cache_row_bytes",
           "state_leaves", "slot_axes", "bytes_by_kind", "check_row"]


class DecodeModel:
    """Base adapter; subclasses implement the protocol documented in the
    module docstring. ``name`` is the registry key."""

    name = None
    #: {option: reason} of the engines this adapter does not serve
    not_served = {}

    # -- required ----------------------------------------------------------
    def check_config(self, cfg):
        raise NotImplementedError

    def compute_dtype(self, dtype):
        raise NotImplementedError

    def extract_params(self, model, who):
        raise NotImplementedError

    def decode_fns(self, cfg, aux, cache_dtype=None, tp_axis=None,
                   tp_size=1):
        raise NotImplementedError

    def matches(self, model):
        raise NotImplementedError

    # -- optional (dense-only adapters may leave these) --------------------
    def tp_setup(self, tp_mesh, cfg, params):
        raise NotImplementedError(
            f"decode model {self.name!r} does not support tensor-parallel "
            "serving")

    def tp_wrap(self, run, tp_mesh, tp_specs, n_extra_in, out_specs,
                in_specs=None, donate=()):
        raise NotImplementedError(
            f"decode model {self.name!r} does not support tensor-parallel "
            "serving")

    def cache_spec(self, cfg):
        """Default spec: opaque pytree pair, described minimally."""
        return {"kind": "kv_pair", "layout": "adapter-defined",
                "quantized": None}

    def kv_read_tile(self, cfg, side, dtype, tp_size=1):
        """The width of the tiles a one-token decode step reads each row
        of ``side`` in, up to the row's position; None: all of it."""
        return None

    def kv_tiles_written(self, cfg, side, dtype, tp_size=1):
        """How many of those tiles such a step's attention writes back a
        row, the step's column in them; 0: the store is not its work."""
        return 0

    def chunk_kv_tile(self, cfg, side, dtype, width):
        """(tile, layers) where a prefill chunk of `width` queries over a
        row of ``side`` walks the row's live key tiles of `tile` columns
        through a kernel, once in each of `layers` layers; None: no
        kernel's walk."""
        return None

    # -- optional (multi-LoRA batched decode, FLAGS_paged_kv engines) ------
    def lora_init(self, cfg, n_slots, rank, dtype=None):
        """Zero-filled stacked adapter pytree for ``n_slots`` adapter
        slots at ``rank`` (slot 0 is reserved all-zero = base requests);
        the pytree feeds ``fwd(..., lora=, adapter_ids=)``."""
        raise NotImplementedError(
            f"decode model {self.name!r} does not support multi-LoRA "
            "serving")

    def lora_pack(self, cfg, exported, rank):
        """One exported adapter (``incubate.lora.export_lora`` form) ->
        the per-slot update written into the stacked pytree: same tree
        shape as one ``lora_init`` slot, factors zero-padded to ``rank``
        (an exact-zero pad — padded lanes contribute nothing)."""
        raise NotImplementedError(
            f"decode model {self.name!r} does not support multi-LoRA "
            "serving")


# name -> DecodeModel instance. Model modules register themselves at
# import; the _LAZY table lets the serving tier resolve a bundled family
# without the caller having imported its module first.
_REGISTRY = {}
_LAZY = {"gpt": "paddle_tpu.models.gpt",
         "solar_open2": "paddle_tpu.models.solar_open2",
         "axk1": "paddle_tpu.models.axk1"}


def register_decode_model(adapter, clobber=False):
    """Register a :class:`DecodeModel` instance under ``adapter.name``.
    Re-registering an existing name raises unless ``clobber=True`` (a
    silent overwrite could swap the serving math under a live engine)."""
    name = getattr(adapter, "name", None)
    if not name:
        raise ValueError("decode-model adapter needs a non-empty .name")
    if name in _REGISTRY and not clobber:
        raise ValueError(
            f"decode model {name!r} is already registered "
            f"({type(_REGISTRY[name]).__name__}); pass clobber=True to "
            "replace it")
    _REGISTRY[name] = adapter
    return adapter


def _materialize(name):
    if name not in _REGISTRY and name in _LAZY:
        importlib.import_module(_LAZY[name])   # module registers itself
    return _REGISTRY.get(name)


def get_decode_model(name):
    """The registered adapter for ``name``; imports a bundled family's
    module lazily. Raises ``KeyError`` with the known names."""
    adapter = _materialize(name)
    if adapter is None:
        known = sorted(set(_REGISTRY) | set(_LAZY))
        raise KeyError(
            f"no decode model registered under {name!r}; known: {known}")
    return adapter


def registered_decode_models():
    """Tuple of registered names (lazy bundled families included)."""
    return tuple(sorted(set(_REGISTRY) | set(_LAZY)))


def resolve(model, spec=None):
    """The adapter serving ``model``: ``spec`` may be a registry name, a
    DecodeModel instance, or None (probe every adapter's ``matches``)."""
    if isinstance(spec, DecodeModel):
        return spec
    if spec is not None:
        return get_decode_model(spec)
    for name in registered_decode_models():
        adapter = _materialize(name)
        if adapter is not None and adapter.matches(model):
            return adapter
    raise TypeError(
        f"no registered decode model serves {type(model).__name__}; "
        f"known: {sorted(registered_decode_models())} — register a "
        "DecodeModel adapter (see paddle_tpu/serving/decode_model.py) or "
        "pass decode_model= explicitly")


def cache_row_bytes(row):
    """Total device bytes of one handoff unit (any cache pytree: a
    (kc, vc) pair, one side, or a quantized (values, scales) tuple) —
    the payload accounting behind ``kv_handoff_bytes_total``."""
    import jax

    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(row)))


# -- the cache as a described tree of state kinds ------------------------------

_PAIR = ({"path": (0,), "kind": "kv", "slot_axis": 1},
         {"path": (1,), "kind": "kv", "slot_axis": 1})


def state_leaves(spec):
    """The leaf descriptions of a ``cache_spec``; a spec that has none is
    a K/V pair with its slots on axis 1."""
    return tuple(spec.get("leaves") or _PAIR)


def _describe(leaves, path):
    """The description covering the leaf at ``path``: the longest
    described path that is a prefix of it."""
    best = None
    for leaf in leaves:
        lp = tuple(leaf["path"])
        if path[:len(lp)] == lp and (best is None
                                     or len(lp) > len(best["path"])):
            best = leaf
    if best is None:
        raise KeyError(f"cache leaf {path} has no description in the "
                       "adapter's cache_spec")
    return best


def _with_paths(tree, root=()):
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = [root + tuple(getattr(k, "key", getattr(k, "idx", None))
                         for k in path) for path, _ in flat]
    return keys, [leaf for _, leaf in flat], treedef


def slot_axes(leaves, half, tree):
    """``tree`` (half ``half`` of the cache pair) with every leaf's slot
    axis in the leaf's place."""
    keys, _, treedef = _with_paths(tree, (half,))
    return treedef.unflatten([int(_describe(leaves, k)["slot_axis"])
                              for k in keys])


def bytes_by_kind(leaves, pair):
    """{kind: device bytes} of a cache pair."""
    keys, arrays, _ = _with_paths(tuple(pair))
    out = {}
    for k, a in zip(keys, arrays):
        kind = _describe(leaves, k)["kind"]
        out[kind] = out.get(kind, 0) + int(a.size * a.dtype.itemsize)
    return out


def check_row(leaves, template, row):
    """Hold a hand-off row to the one-slot tree ``template`` (shapes and
    dtypes, e.g. from ``jax.eval_shape``): the same tree, every leaf's
    shape and dtype. Raises ``ValueError`` naming the leaf."""
    want_k, want, want_def = _with_paths(tuple(template))
    _, got, got_def = _with_paths(tuple(row))
    if want_def != got_def:
        raise ValueError(f"hand-off row is a {got_def}, this engine's state "
                         f"is a {want_def}")
    for k, w, g in zip(want_k, want, got):
        if tuple(w.shape) != tuple(g.shape) or w.dtype != g.dtype:
            kind = _describe(leaves, k)["kind"]
            raise ValueError(
                f"hand-off row leaf {k} ({kind}): {g.dtype}{tuple(g.shape)}"
                f", this engine holds {w.dtype}{tuple(w.shape)}")
