"""The device's time under the program's own names (docs/OBSERVABILITY.md
"Device scopes"), the program half, on the CPU at tiny sizes: every jitted
program of the three decode families and the trainer's step carries the
`jax.named_scope` words it should and no other, is a module named
`<site>.<label>`, and is the same program with the scopes taken away (the
lowered text differs in its locations alone, the outputs not at all).

The programs are taken where the engine itself hands them out: `warmup()`
with `CachedJit.warm` recording (program, shape specs) in place of
compiling."""
import contextlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import aot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: every component a `jax.named_scope` of the decode families may carry
WORDS = {"embed", "attn", "proj", "core", "kda", "conv", "state", "mlp",
         "moe", "router", "experts", "shared", "combine", "cache", "store",
         "admit", "head", "pick", "loss", "optimizer", "grad_sync"}

_BLOCK = {"embed", "attn/proj", "attn/core", "cache/store", "head"}
_MOE = {"moe", "moe/router", "moe/experts", "moe/shared", "moe/combine"}
_FAMILY = {"gpt": _BLOCK | {"mlp"},
           "solar_open2": _BLOCK | _MOE | {"kda/proj", "kda/conv",
                                           "kda/state"},
           "axk1": _BLOCK | _MOE | {"mlp"}}
#: program label -> the scopes it holds beside its family's block
_PROGRAM = {"prefill": {"cache/admit"}, "prefill_chunk": set(),
            "step_greedy": {"pick"}, "step_sample": {"pick"}}
#: ... or alone (copy_cache: the copy XLA makes of an undonated argument, no
#: operation of the program's own)
_ALONE = {"admit": {"cache/admit"}, "admit_fixed": {"cache/admit"},
          "copy_cache": set(), "pick1_put": {"pick"}}


def _gpt():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    return GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=64, dropout=0.0))


def _solar_open2():
    from paddle_tpu.models import SolarOpen2Config, SolarOpen2ForCausalLM

    return SolarOpen2ForCausalLM(SolarOpen2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        kda_num_heads=2, kda_head_dim=8, n_routed_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=16, max_seq_len=64))


def _axk1():
    from paddle_tpu.models import AXK1Config, AXK1ForCausalLM

    return AXK1ForCausalLM(AXK1Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=2, q_lora_rank=12, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        n_routed_experts=4, num_experts_per_tok=2, max_seq_len=64))


MODELS = {"gpt": _gpt, "solar_open2": _solar_open2, "axk1": _axk1}


class _NoScope(contextlib.ContextDecorator):
    """`jax.named_scope` taken away: a context and a decorator, as it is."""

    def __init__(self, name):
        pass

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


def _programs(family, monkeypatch, scoped=True):
    """{label: (CachedJit, specs)} of a fresh engine of the family; with
    `scoped` False built and lowered with `jax.named_scope` a null
    context. Every name handed to `jax.named_scope` on the way is noted
    under `names`."""
    from paddle_tpu.inference.serving import ServingEngine

    names = set()
    real = jax.named_scope

    def noting(name):
        names.add(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", noting if scoped else _NoScope)
    paddle.seed(0)
    model = MODELS[family]()
    model.eval()
    eng = ServingEngine(model, max_batch=2, prompt_buckets=(16,),
                        prefill_chunk=8)
    taken = {}

    def warm(self, *specs):
        taken.setdefault(self._label, (self, specs))
        return False

    monkeypatch.setattr(aot.CachedJit, "warm", warm)
    eng.warmup()
    return taken, names


def _lower(cj, specs, debug):
    return cj.lower(*aot._canonical_specs(specs)).as_text(debug_info=debug)


def _scopes_in(text):
    from benchmark import scopes

    return {scopes.scope_of(path)[0]
            for path in re.findall(r'loc\("(jit\([^"]+)"', text)}


def _cases():
    for family in MODELS:
        for label in list(_PROGRAM) + list(_ALONE):
            if label == "admit_fixed" and family != "solar_open2":
                continue       # one admit serves a pair of K/V halves
            yield family, label


@pytest.mark.parametrize("family, label", list(_cases()))
def test_a_program_holds_its_scopes_and_its_name(family, label, monkeypatch):
    taken, names = _programs(family, monkeypatch)
    assert label in taken, sorted(taken)
    cj, specs = taken[label]
    text = _lower(cj, specs, debug=True)
    # the module is the pair the cost registry keys on
    assert f"module @jit_serving.{label} " in text
    want = _ALONE[label] if label in _ALONE \
        else _FAMILY[family] | _PROGRAM[label]
    found = _scopes_in(text) - {"unscoped"}
    # a parent word shows where an operation lies under it alone
    assert want - {"moe"} <= found <= want | {"attn", "kda", "moe"}, (
        sorted(want - found), sorted(found - want))
    # and nothing the engine or the family names lies outside the words
    stray = {part for name in names for part in name.split("/")} - WORDS
    assert not stray, stray


@pytest.mark.parametrize("family", list(MODELS))
def test_scopes_are_metadata_and_nothing_else(family, monkeypatch):
    """Every program of the family, lowered with and without the scopes:
    the same text once the locations are left out, and bit-equal outputs
    on the same inputs."""
    with monkeypatch.context() as mp:
        scoped, _ = _programs(family, mp)
        texts = {label: _lower(cj, specs, debug=False)
                 for label, (cj, specs) in scoped.items()}
        with_debug = _lower(*scoped["step_greedy"], debug=True)
    with monkeypatch.context() as mp:
        bare, names = _programs(family, mp, scoped=False)
        assert not names
        assert set(bare) == set(texts)
        for label, (cj, specs) in bare.items():
            assert _lower(cj, specs, debug=False) == texts[label], label
        assert _scopes_in(_lower(*bare["step_greedy"], debug=True)) \
            == {"unscoped"}
        assert "attn/core" in _scopes_in(with_debug)

    rng = np.random.default_rng(0)

    def arrays(specs):
        def one(s):
            if not hasattr(s, "shape"):
                return s
            if jnp.issubdtype(s.dtype, jnp.integer):
                return jnp.asarray(rng.integers(0, 8, s.shape), s.dtype)
            return jnp.asarray(rng.standard_normal(s.shape), s.dtype)
        return jax.tree_util.tree_map(one, specs)

    for label in ("prefill", "step_greedy", "prefill_chunk", "admit"):
        args = arrays(aot._canonical_specs(scoped[label][1]))
        copy = jax.tree_util.tree_map(
            lambda a: jnp.array(a) if hasattr(a, "shape") else a, args)
        got = scoped[label][0]._jit(*args)
        ref = bare[label][0]._jit(*copy)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), label


def _trainer(monkeypatch, scoped=True):
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.models.gpt import GPTPretrainLoss

    if not scoped:
        monkeypatch.setattr(jax, "named_scope", _NoScope)
    paddle.seed(0)
    model = _gpt()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(), mesh=mesh)


def _lowered_step(tr):
    from paddle_tpu.core.generator import default_generator

    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    lr = jnp.asarray(1e-3, jnp.float32)
    rng = default_generator().fold_in(0)
    return tr._build([ids, ids]).lower(tr.params, tr.opt_state, tr.buffers,
                                       lr, rng, ids, ids)


def test_the_trainers_step(monkeypatch):
    """`train.step`: the vocabulary by direction, with the Layer tree's
    registered names around it; without the scopes the same program and
    the same losses."""
    from benchmark import scopes

    with monkeypatch.context() as mp:
        tr = _trainer(mp)
        low = _lowered_step(tr)
        text, plain = low.as_text(debug_info=True), low.as_text()
        ids = np.arange(32, dtype=np.int32).reshape(2, 16) % 64
        losses = [float(tr.train_step(ids, ids)) for _ in range(3)]
    assert "module @jit_train.step " in text
    paths = set(re.findall(r'loc\("(jit\([^"]+)"', text))
    found = {scopes.scope_of(p) for p in paths}
    for scope in ("embed", "attn", "attn/proj", "attn/core", "mlp", "head",
                  "loss"):
        assert (scope, "fwd") in found and (scope, "bwd") in found, scope
    assert ("optimizer", "none") in found
    # `nn.Layer.__call__` nests as registered: a block's sublayers under
    # the list's name, no index, the class name at the root
    assert any("jvp(GPTForCausalLM))/gpt/blocks/attn/core/" in p
               for p in paths)
    assert any("jvp(GPTForCausalLM)/gpt/blocks/mlp/fc1/" in p for p in paths)
    assert not any(re.search(r"/blocks/\d", p) for p in paths)
    with monkeypatch.context() as mp:
        bare = _trainer(mp, scoped=False)
        assert _lowered_step(bare).as_text() == plain
        assert [float(bare.train_step(ids, ids)) for _ in range(3)] == losses


@pytest.mark.parametrize("build, name", [
    ("_build_localsgd", "train.step_localsgd"),
    ("_build_dgc", "train.step_dgc"),
    ("_build_dp_compressed", "train.step_dp_compressed")])
def test_the_trainers_other_steps_are_named(build, name):
    """The three other builders of distributed/spmd.py name their program
    after themselves (read from the source: building each needs its own
    optimizer and mesh, which their own tests bring)."""
    import inspect

    from paddle_tpu.distributed.spmd import SpmdTrainer

    src = inspect.getsource(getattr(SpmdTrainer, build))
    assert f'_aot.named(step, "{name}")' in src
    assert 'jax.named_scope("optimizer")' in src
    assert 'jax.named_scope("grad_sync")' in src


def test_layers_run_under_their_registered_names():
    from paddle_tpu import nn

    class Leaf(nn.Layer):
        def forward(self, x):
            return x * 2.0

    class Pair(nn.Layer):
        def __init__(self):
            super().__init__()
            self.left = Leaf()
            self.items = nn.LayerList([Leaf(), Leaf()])
            self.seq = nn.Sequential(Leaf(), Leaf())

        def forward(self, x):
            x = self.left(x)
            for item in self.items:
                x = item(x)
            return self.seq(x)

    root = Pair()
    root.items.append(Leaf())
    assert [root.left._scope, root.items._scope, root.items[2]._scope,
            root.seq[1]._scope, root._scope] \
        == ["left", "items", "items", "seq", None]
    text = jax.jit(lambda a: root(paddle.Tensor(a))._data).lower(
        jnp.ones((2,))).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("jit\([^"]*\)/([^"]+)/mul"', text))
    assert paths == {"Pair/left", "Pair/items", "Pair/seq/seq"}, paths
