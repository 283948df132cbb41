"""Compile the main path for a described TPU v5e chip, at qwen2.5-0.5b's
and qwen3-4b's published widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks that break the
(8, 128) tiling and kernels that need more VMEM than a core has. Compiling
here catches both at no chip time. Nothing runs, so these tests say nothing
about values or speed; ``chip_smoke.py`` does that on the chip.

The topology is described inside a fixture (never at import: only one
process may load the TPU library, and every test worker imports this file),
and every compile stays in this one file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import engine as engine_lib
from repro.core import injection
from repro.kernels import ops
from repro.launch import sharding as shard_lib
from repro.launch.mesh import make_lane_mesh
from repro.models import model as model_lib
from repro.serving.sampler import SamplingParams

CFG = get_config("qwen2.5-0.5b")
H, HKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.d_head
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not a fallback
    return compiled


def _kernel_calls(text: str) -> set[str]:
    """Base names of the Pallas custom calls in compiled HLO text: the names
    the trace's op events carry (``%synapse_attention.8 = ...``)."""
    return set(re.findall(
        r'%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"', text))


def _module_name(text: str) -> str:
    return re.match(r"HloModule ([^,\s]+)", text).group(1)


@pytest.mark.parametrize("B,T", [(8, 64 + 64 + 16), (2, 512)])
def test_synapse_attention_compiles(one_chip, B, T):
    s = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(
        lambda q, k, v, m: ops.synapse_attention(q, k, v, m, interpret=False),
        s((B, H, D)), s((B, T, HKV, D)), s((B, T, HKV, D)), s((B, T), jnp.bool_),
    )


@pytest.mark.parametrize("model,B", [("qwen2.5-0.5b", 256), ("qwen3-4b", 64)])
def test_synapse_attend_reads_pieces_in_place(one_chip, monkeypatch, model, B):
    """The side pass's attend at the council's shapes (64 landmarks, 64
    window and 16 inject slots, in the side cache's stacks of every layer;
    256 lanes at qwen2.5-0.5b, 64 at qwen3-4b, whose 8 kv heads of 128
    make the widest row) at a traced layer: one kernel call over the three
    pieces, within the chip's VMEM, with no pad, no concatenate and no
    slice of a layer in front of it."""
    monkeypatch.setattr(ops, "INTERPRET", False)
    cfg = get_config(model)
    sizes, width = (64, 64, 16), cfg.n_kv_heads * cfg.d_head
    s = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    stacks = [(s((cfg.n_layers, B, T, width)), s((cfg.n_layers, B, T, width))) for T in sizes]
    valids = [s((B, T), jnp.bool_) for T in sizes]
    text = _compile(lambda q, p, m, layer: ops.synapse_attend(q, p, m, layer=layer),
                    s((B, cfg.n_heads, cfg.d_head)), stacks, valids, s((), jnp.int32)).as_text()
    calls = re.findall(r'%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert calls == ["synapse_attention"]
    ops_used = set(re.findall(r"= [^\n=]*? ([a-z][\w-]*)\(", text))
    assert not {"pad", "concatenate", "dynamic-slice"} & ops_used, sorted(ops_used)
    assert not re.search(rf"= bf16\[{B},(64|16),{width}\]", text)  # no copy of one layer's piece


@pytest.mark.parametrize("n_landmarks", [0, 8], ids=["density_only", "landmarks"])
def test_landmark_score_compiles(one_chip, n_landmarks):
    B, T = 4, 512
    s = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = [s((B, H, D)), s((B, T, HKV, D)), s((B, T), jnp.bool_)]
    if n_landmarks:
        args.append(s((B, n_landmarks, D)))
        fn = lambda q, k, m, lm: ops.landmark_score(q, k, lm, m, interpret=False)
    else:
        fn = lambda q, k, m: ops.landmark_score(q, k, None, m, interpret=False)
    _compile(fn, *args)


MAIN_SPEC = model_lib.CacheSpec(kind="full", capacity=512)
SIDE_SPEC = model_lib.CacheSpec(kind="synapse", n_landmarks=64, window=64, n_inject=16)


def _council_state():
    """Shapes of the council engine's TickState: 2 rivers, 8 side lanes."""
    greedy = SamplingParams(greedy=True)
    return jax.eval_shape(lambda: engine_lib.init_tick_state(
        CFG, n_main=2, max_side=8, main_spec=MAIN_SPEC, side_spec=SIDE_SPEC,
        ring_capacity=8, side_prompt_cap=64, main_sampling=greedy, side_sampling=greedy,
    ))


def _placed(tree, shardings):
    return jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree, shardings
    )


def test_macro_window_compiles(one_chip, monkeypatch):
    """One 8-tick fused window of the council engine (2 rivers, 8 side
    lanes, bf16): the program every chip tick runs, kernels inlined."""
    # code that asks the backend sees the CPU here: steer it to the chip path
    monkeypatch.setattr(ops, "INTERPRET", False)
    place = lambda tree: _placed(tree, jax.tree.map(lambda _: one_chip, tree))
    state = place(_council_state())
    params = place(jax.eval_shape(
        lambda: model_lib.cast_params(model_lib.init_params(jax.random.key(0), CFG), CFG)
    ))
    window = functools.partial(
        engine_lib.fused_tick, cfg=CFG, main_spec=MAIN_SPEC,
        side_spec=SIDE_SPEC, step_sides=True, use_filters=False, any_greedy=True, n_ticks=8,
    )
    compiled = _compile(window, params, state)
    assert "synapse_attention" in _kernel_calls(compiled.as_text())
    mem = compiled.memory_analysis()
    # weights (bf16, ~0.99 GB) dominate; the window must fit one 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_lane_mesh_spawn_compiles(topo, monkeypatch):
    """Spawning a side on a 4-chip lane mesh: GSPMD cannot partition the
    landmark kernel, so the compression must sit inside a shard_map while
    the scatter into the lane-sharded side caches stays partitioned."""
    monkeypatch.setattr(ops, "INTERPRET", False)
    mesh = make_lane_mesh(4, devices=topo.devices)
    state = _council_state()
    shardings = shard_lib.shardings_for(shard_lib.tick_state_specs(state, mesh), mesh)
    lane = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    spawn = engine_lib.spawn_program(CFG, SIDE_SPEC, mesh=mesh)
    _compile(
        spawn, _placed(state.main_caches, shardings.main_caches),
        _placed(state.side_caches, shardings.side_caches), lane, lane,
    )


def test_spawn_program_compiles_under_its_name(one_chip, monkeypatch):
    """The engine's spawn program on one chip: the trace finds it by its
    module name and its landmark kernel by the custom call's name."""
    monkeypatch.setattr(ops, "INTERPRET", False)
    place = lambda tree: _placed(tree, jax.tree.map(lambda _: one_chip, tree))
    state = _council_state()
    lane = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(engine_lib.spawn_program(CFG, SIDE_SPEC),
                        place(state.main_caches), place(state.side_caches), lane, lane)
    text = compiled.as_text()
    assert _module_name(text) == "jit_engine_spawn"
    assert _kernel_calls(text) == {"landmark_score"}


def test_qwen3_4b_council_fits_one_chip(one_chip, monkeypatch):
    """qwen3-4b's council (2 rivers of 2304 slots, 64 side lanes, bf16
    weights held once): the 8-tick window, with the side attend in one
    kernel call, and a drain's batched merge of 32 thoughts, which runs
    them through all 36 layers, each fit one 16 GB chip with the weights
    and caches it holds. The merge writes the thoughts into the donated
    river caches in place: its temporaries hold no copy of them."""
    monkeypatch.setattr(ops, "INTERPRET", False)
    cfg = dataclasses.replace(get_config("qwen3-4b"), param_dtype="bfloat16")
    main_spec = model_lib.CacheSpec(kind="full", capacity=2304)
    greedy = SamplingParams(greedy=True)
    place = lambda tree: _placed(tree, jax.tree.map(lambda _: one_chip, tree))
    state = place(jax.eval_shape(lambda: engine_lib.init_tick_state(
        cfg, n_main=2, max_side=64, main_spec=main_spec, side_spec=SIDE_SPEC,
        ring_capacity=8, side_prompt_cap=64, main_sampling=greedy, side_sampling=greedy,
    )))
    params = place(model_lib.abstract_params(cfg))
    window = functools.partial(
        engine_lib.fused_tick, cfg=cfg, main_spec=main_spec, side_spec=SIDE_SPEC,
        step_sides=True, use_filters=False, any_greedy=True, n_ticks=8,
    )
    compiled = _compile(window, params, state)
    assert _kernel_calls(compiled.as_text()) == {"synapse_attention"}
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    K = engine_lib.MERGE_BATCH
    merge = jax.jit(
        lambda p, mc, mh, toks, parent, vpos, valid: injection.merge_thoughts(
            p, cfg, mc, mh, toks, parent, vpos, valid, -1.0),
        donate_argnums=(1,),
    ).lower(params, state.main_caches, state.main_hidden, s((K, 16), jnp.int32),
            s((K,), jnp.int32), s((K,), jnp.int32), s((K,), jnp.bool_)).compile()
    for c in (compiled, merge):
        mem = c.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    river_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state.main_caches))
    assert merge.memory_analysis().temp_size_in_bytes < river_bytes
