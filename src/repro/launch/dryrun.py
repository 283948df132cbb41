"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production meshes, record memory/cost analysis + collective bytes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

This is the ONLY entry point that forces 512 host devices (the two lines
below run before any other import, per the multi-pod dry-run contract);
smoke tests and benches see the real single CPU device.
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

import argparse
import dataclasses
import functools
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.core import synapse_sharded
from repro.launch import sharding as shard_lib
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.training.optimizer import AdamWConfig
from repro.training.trainer import abstract_train_state, make_train_step

SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over all array shapes found in an HLO type string."""
    total = 0
    for dt, dims in SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    """computation name -> its body lines."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        ls = line.rstrip()
        m = re.match(r"\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->.*\{\s*$", ls)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if ls.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(ls.strip())
    return comps


def parse_collectives(hlo_text: str) -> dict:
    """Collective output bytes with while-loop trip-count attribution.

    Computations form a call graph; while-op bodies get multiplier =
    caller_mult * trip_count, where the trip count is recovered from the
    loop condition's comparison constant (scan loops always have one).
    """
    comps = _split_computations(hlo_text)

    # per-computation: collectives, while-calls (body, cond), other calls
    coll: dict[str, list[tuple[str, int]]] = {}
    whiles: dict[str, list[tuple[str, str]]] = {}
    calls: dict[str, list[str]] = {}
    for name, lines in comps.items():
        for ls in lines:
            if "=" not in ls:
                continue
            rhs = ls.split("=", 1)[1]
            for kind in _COLL_KINDS:
                if re.search(rf"\b{kind}(?:-start)?\(", rhs):
                    b = _shape_bytes(rhs.split(kind)[0])
                    coll.setdefault(name, []).append((kind, b))
                    break
            wm = re.search(r"\bwhile\(.*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+)", rhs)
            if not wm:
                wm2 = re.search(r"\bwhile\(.*?body=%?([\w.\-]+).*?condition=%?([\w.\-]+)", rhs)
                if wm2:
                    whiles.setdefault(name, []).append((wm2.group(1), wm2.group(2)))
            else:
                whiles.setdefault(name, []).append((wm.group(2), wm.group(1)))
            for cm in re.finditer(r"(?:calls|to_apply|fusion)=%?([\w.\-]+)", rhs):
                calls.setdefault(name, []).append(cm.group(1))

    def trip_count(cond_name: str) -> int:
        consts = []
        for ls in comps.get(cond_name, []):
            for c in re.finditer(r"constant\((\d+)\)", ls):
                consts.append(int(c.group(1)))
        return max(consts) if consts else 1

    # propagate multipliers from ENTRY
    entry = next((n for n in comps if "main" in n or n.startswith("entry")), None)
    if entry is None:
        entry = max(comps, key=lambda n: len(comps[n])) if comps else None
    mult: dict[str, int] = {}

    def visit(name: str, m: int):
        if m <= mult.get(name, 0):
            return
        mult[name] = m
        for body, cond in whiles.get(name, []):
            visit(body, m * max(trip_count(cond), 1))
            visit(cond, m)
        for callee in calls.get(name, []):
            visit(callee, m)

    if entry:
        visit(entry, 1)

    per_kind: dict[str, int] = {}
    total_once = 0
    total = 0
    for name, ops in coll.items():
        m = mult.get(name, 1)
        for kind, b in ops:
            per_kind[kind] = per_kind.get(kind, 0) + b * m
            total_once += b
            total += b * m
    return {"per_kind": per_kind, "total_bytes_once": total_once, "total_bytes": total}


def while_trip_counts_from_config(cfg) -> int:
    return cfg.n_layers


def build_lowerable(arch: str, shape_name: str, mesh, *, act_mode: str = "auto", fsdp_on: bool = True, synapse_token_shard: bool = True):
    """Returns (fn, args, in_shardings, out_shardings, plan).

    act_mode: "auto" -> sequence-parallel saves for full-seq kinds, batch-only
    for decode; "batch" -> batch-only; "off" -> no activation constraints.
    """
    cfg = get_config(arch)
    plan = specs_lib.plan_for(cfg, shape_name)
    if plan.skip:
        return None, None, None, None, plan
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    if act_mode == "off":
        model_lib.set_activation_sharding(None)
    elif plan.kind == "decode" or act_mode == "batch":
        model_lib.set_activation_sharding(P(dp, None, None))
    else:
        # sequence-parallel layer-boundary saves (Megatron-SP style)
        model_lib.set_activation_sharding(P(dp, "model", None))
    # flash-decode shard_map attend over token-sharded synapse buffers: the
    # scoped token_sharding context must be LIVE while the fn traces (the
    # jit.lower call happens in run_one), so wrap rather than set globally
    tok_axis = "model" if (plan.cache_kind == "synapse" and synapse_token_shard) else None

    def _scoped(fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            with synapse_sharded.token_sharding(tok_axis, mesh=mesh):
                return fn(*a, **k)

        return wrapped

    if plan.kind == "train":
        state_abs = abstract_train_state(cfg)
        batch_abs = specs_lib.train_batch_specs(cfg, plan.seq, plan.batch)
        state_spec = shard_lib.param_specs(state_abs, cfg, mesh, fsdp_on=fsdp_on)
        batch_spec = shard_lib.batch_specs(batch_abs, cfg, mesh)
        opt_cfg = AdamWConfig()
        step_fn = _scoped(make_train_step(cfg, opt_cfg))
        out_spec = (state_spec, jax.tree.map(lambda _: P(), {
            "loss": 0, "ce": 0, "lb_loss": 0, "drop_frac": 0, "grad_norm": 0, "lr": 0}))
        return step_fn, (state_abs, batch_abs), (state_spec, batch_spec), out_spec, plan

    params_abs = model_lib.abstract_params(cfg)
    params_spec = shard_lib.param_specs(params_abs, cfg, mesh, fsdp_on=fsdp_on)

    if plan.kind == "prefill":
        inputs_abs, cache_spec = specs_lib.input_specs(cfg, plan)
        inputs_spec = shard_lib.batch_specs(inputs_abs, cfg, mesh)
        if cfg.is_encoder_only:
            fn = _scoped(lambda p, i: model_lib.forward(p, cfg, i))
            out = (params_spec, inputs_spec)
            return fn, (params_abs, inputs_abs), out, (P(), {"lb_loss": P(), "drop_frac": P(), "hidden_last": P()}), plan
        caches_abs = jax.eval_shape(lambda: model_lib.init_caches(cfg, plan.batch, cache_spec))
        caches_spec = shard_lib.cache_specs(caches_abs, cfg, mesh, synapse_token_shard=synapse_token_shard)
        fn = _scoped(lambda p, i, c: model_lib.prefill(p, cfg, i, c, spec=cache_spec))
        out_spec = (
            shard_lib.fit_spec(mesh, (plan.batch, cfg.vocab_size), [dp, None]),
            shard_lib.fit_spec(mesh, (plan.batch, cfg.d_model), [dp, None]),
            caches_spec,
        )  # logits, hidden, caches
        return (
            fn,
            (params_abs, inputs_abs, caches_abs),
            (params_spec, inputs_spec, caches_spec),
            out_spec,
            plan,
        )

    # decode
    inputs_abs, cache_spec = specs_lib.input_specs(cfg, plan)
    inputs_spec = shard_lib.batch_specs(inputs_abs, cfg, mesh)
    caches_abs = jax.eval_shape(lambda: model_lib.init_caches(cfg, plan.batch, cache_spec))
    caches_spec = shard_lib.cache_specs(caches_abs, cfg, mesh, synapse_token_shard=synapse_token_shard)
    fn = _scoped(lambda p, i, c: model_lib.decode_step(p, cfg, i, c, spec=cache_spec))
    out_spec = (
        shard_lib.fit_spec(mesh, (plan.batch, cfg.vocab_size), [dp, None]),
        shard_lib.fit_spec(mesh, (plan.batch, cfg.d_model), [dp, None]),
        caches_spec,
    )  # logits, hidden, caches
    return (
        fn,
        (params_abs, inputs_abs, caches_abs),
        (params_spec, inputs_spec, caches_spec),
        out_spec,
        plan,
    )


def run_one(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str | None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    t0 = time.time()
    try:
        fn, args, in_specs, out_specs, plan = build_lowerable(arch, shape_name, mesh)
        if plan.skip:
            rec.update(status="SKIP", reason=plan.skip)
            print(f"[dryrun] {arch} x {shape_name} on {mesh_name}: SKIP ({plan.skip})")
            if out_dir:
                os.makedirs(out_dir, exist_ok=True)
                with open(os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json"), "w") as f:
                    json.dump(rec, f, indent=1, default=str)
            return rec
        with mesh:
            in_sh = shard_lib.shardings_for(in_specs, mesh)
            out_sh = shard_lib.shardings_for(out_specs, mesh)
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = parse_collectives(hlo)
        rec.update(
            status="OK",
            kind=plan.kind,
            cache_kind=plan.cache_kind,
            seq=plan.seq,
            batch=plan.batch,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=_mem_dict(mem),
            cost={k: v for k, v in (cost or {}).items() if isinstance(v, (int, float))},
            collectives=coll,
            hlo_bytes=len(hlo),
        )
        print(
            f"[dryrun] {arch} x {shape_name} on {mesh_name}: OK "
            f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
            f"argbytes/dev {rec['memory'].get('argument_size_in_bytes', 0)/1e9:.2f}GB, "
            f"temp/dev {rec['memory'].get('temp_size_in_bytes', 0)/1e9:.2f}GB)"
        )
    except Exception as e:  # a failure here is a bug in the system
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}", traceback=traceback.format_exc()[-2000:])
        print(f"[dryrun] {arch} x {shape_name} on {mesh_name}: FAIL {type(e).__name__}: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def run_lane(n_side: int, *, n_devices: int = 8, sync_every: int = 8,
             out_dir: str | None = None) -> dict:
    """Abstract lower + compile of the LANE-SHARDED macro tick (ISSUE 6).

    Builds the exact TickState the engine would hold at ``max_side=n_side``
    via ``jax.eval_shape`` (no buffers materialize — this is how the
    1024-lane shape dry-runs on the container), wraps the fused window in
    ``shard_map`` over a lane mesh, and records memory/collective analysis.
    """
    from repro.core import engine as engine_lib
    from repro.launch.mesh import make_lane_mesh
    from repro.serving.sampler import SamplingParams

    cfg = get_config("qwen2.5-0.5b", reduced=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    jcfg = dataclasses.replace(cfg, scan_layers=cfg.scan_layers and cfg.n_layers > 8)
    mesh = make_lane_mesh(n_devices)
    main_spec = model_lib.CacheSpec(kind="full", capacity=128)
    side_spec = model_lib.CacheSpec(kind="synapse", n_landmarks=64, window=64, n_inject=16)
    side_spec = dataclasses.replace(
        side_spec,
        policy=dataclasses.replace(side_spec.policy, attend_impl="piece"),
    )
    greedy = SamplingParams(greedy=True)
    state_abs = jax.eval_shape(
        lambda: engine_lib.init_tick_state(
            cfg, n_main=1, max_side=n_side, main_spec=main_spec,
            side_spec=side_spec, ring_capacity=sync_every, side_prompt_cap=64,
            main_sampling=greedy, side_sampling=greedy,
        )
    )
    params_abs = model_lib.abstract_params(cfg)
    specs = shard_lib.tick_state_specs(state_abs, mesh)
    fn = synapse_sharded.shard_map_nocheck(
        functools.partial(
            engine_lib.fused_tick, cfg=jcfg, main_spec=main_spec,
            side_spec=side_spec, step_sides=True, use_filters=False,
            any_greedy=True, n_ticks=sync_every,
        ),
        mesh, in_specs=(P(), specs), out_specs=specs,
    )
    rec: dict = {"kind": "lane_macro_tick", "n_side": n_side,
                 "lane_mesh_shape": list(mesh.devices.shape),
                 "sync_every": sync_every}
    t0 = time.time()
    try:
        jitted = jax.jit(fn, donate_argnums=(1,))
        lowered = jitted.lower(params_abs, state_abs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        rec.update(
            status="OK", lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory=_mem_dict(mem), collectives=parse_collectives(hlo),
            hlo_bytes=len(hlo),
        )
        print(
            f"[dryrun] lane macro tick n_side={n_side} on {n_devices}-device "
            f"lane mesh: OK (lower {t_lower:.1f}s compile {t_compile:.1f}s, "
            f"argbytes/dev {rec['memory'].get('argument_size_in_bytes', 0)/1e9:.2f}GB)"
        )
    except Exception as e:
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[dryrun] lane macro tick n_side={n_side}: FAIL {type(e).__name__}: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"lane__s{n_side}__d{n_devices}.json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def run_registry(n_registered: int, *, arch: str = "qwen2.5-0.5b",
                 n_active: int = 8, main_capacity: int = 1024,
                 out_dir: str | None = None) -> dict:
    """Abstract tiered-memory accounting (ISSUE 7): what ``n_registered``
    agents cost when only ``n_active`` hold device lanes.

    Everything is ``eval_shape`` — the per-agent snapshot is the exact
    pytree `CortexEngine.hibernate` gathers (`engine_gather_main` over the
    abstract TickState), so the bytes are the real hibernation payload at
    full `main_capacity`, computed without materializing a single buffer.
    The same math extrapolated to 1M agents is the paper's capacity claim:
    device cost is flat in ``n_registered`` (weights + active lanes only);
    dormant agents ride host RAM and zstd disk. The zstd ratio, when the
    codec is installed, is measured on synthetic float32 noise — a LOWER
    bound (real KV activations compress better than noise)."""
    import math

    from repro.checkpoint import io as ckpt_io
    from repro.core import engine as engine_lib
    from repro.serving.sampler import SamplingParams

    cfg = get_config(arch)
    main_spec = model_lib.CacheSpec(kind="full", capacity=main_capacity)
    side_spec = model_lib.CacheSpec(
        kind="synapse", n_landmarks=64, window=64, n_inject=16
    )
    greedy = SamplingParams(greedy=True)
    state_abs = jax.eval_shape(
        lambda: engine_lib.init_tick_state(
            cfg, n_main=n_active, max_side=8, main_spec=main_spec,
            side_spec=side_spec, ring_capacity=8, side_prompt_cap=64,
            main_sampling=greedy, side_sampling=greedy,
        )
    )
    snap_abs = jax.eval_shape(engine_lib.engine_gather_main, state_abs, 0)

    def abs_bytes(tree) -> int:
        return sum(
            math.prod(x.shape) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(tree)
        )

    per_agent = abs_bytes(snap_abs)
    weight_bytes = abs_bytes(model_lib.abstract_params(cfg))

    zstd_ratio = None
    if ckpt_io.zstandard is not None:
        import numpy as np

        rng = np.random.default_rng(0)
        noise = jax.tree_util.tree_map(
            lambda s: rng.standard_normal(s.shape).astype(s.dtype)
            if s.dtype.kind == "f"
            else rng.integers(0, 2, s.shape).astype(s.dtype),
            snap_abs,
        )
        blob = ckpt_io.dumps(noise)
        zstd_ratio = per_agent / len(blob)

    def tier_table(n: int) -> dict:
        dormant = max(0, n - n_active)
        warm = dormant * per_agent
        return {
            "n_registered": n,
            "device_bytes": weight_bytes + n_active * per_agent,
            "warm_bytes_all_host": warm,
            "cold_bytes_all_disk": (
                int(warm / zstd_ratio) if zstd_ratio else None
            ),
            "device_bytes_if_all_resident": weight_bytes + n * per_agent,
        }

    rec = {
        "kind": "registry_tiers",
        "arch": arch,
        "n_active": n_active,
        "main_capacity": main_capacity,
        "per_agent_snapshot_bytes": per_agent,
        "weight_bytes": weight_bytes,
        "zstd_ratio_noise_floor": zstd_ratio,
        "at_n": tier_table(n_registered),
        "at_1m": tier_table(1_000_000),
    }
    t = rec["at_n"]
    print(
        f"[dryrun] registry {arch}: {n_registered} registered / {n_active} "
        f"active @ capacity {main_capacity}: snapshot/agent "
        f"{per_agent/1e6:.2f}MB; device {t['device_bytes']/1e9:.2f}GB "
        f"(vs {t['device_bytes_if_all_resident']/1e9:.2f}GB all-resident), "
        f"host {t['warm_bytes_all_host']/1e9:.2f}GB"
        + (
            f", disk {t['cold_bytes_all_disk']/1e9:.2f}GB "
            f"(zstd ratio >= {zstd_ratio:.2f})"
            if zstd_ratio
            else " (zstd unavailable: cold tier sized as None)"
        )
    )
    m = rec["at_1m"]
    print(
        f"[dryrun] registry {arch}: extrapolated 1M agents: device "
        f"{m['device_bytes']/1e9:.2f}GB flat, host+disk spill "
        f"{m['warm_bytes_all_host']/1e12:.2f}TB raw — vs "
        f"{m['device_bytes_if_all_resident']/1e12:.2f}TB if all resident"
    )
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"registry__{arch}__{n_registered}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def _mem_dict(mem) -> dict:
    out = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out and isinstance(mem, str):
        out["raw"] = mem[:2000]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(specs_lib.SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--lane", type=int, default=None, metavar="N_SIDE",
                    help="lower+compile the lane-sharded macro tick at N_SIDE "
                         "side lanes on an 8-device lane mesh (ISSUE 6 scale "
                         "dry-run; e.g. --lane 1024)")
    ap.add_argument("--registry", type=int, default=None, metavar="N",
                    help="abstract tiered-memory accounting for N registered "
                         "agents over --registry-active lanes (ISSUE 7; e.g. "
                         "--registry 10000), incl. the 1M-agent extrapolation")
    ap.add_argument("--registry-active", type=int, default=8)
    args = ap.parse_args()

    if args.registry is not None:
        run_registry(args.registry, arch=args.arch or "qwen2.5-0.5b",
                     n_active=args.registry_active, out_dir=args.out)
        return

    if args.lane is not None:
        rec = run_lane(args.lane, out_dir=args.out)
        if rec["status"] != "OK":
            raise SystemExit(1)
        return

    combos = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    archs = [a for a in archs if a != "qwen2.5-0.5b" or args.arch == a]
    shapes = list(specs_lib.SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                combos.append((arch, shape, mp))

    results = [run_one(a, s, multi_pod=mp, out_dir=args.out) for a, s, mp in combos]
    ok = sum(r["status"] == "OK" for r in results)
    skip = sum(r["status"] == "SKIP" for r in results)
    fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\n[dryrun] {ok} OK, {skip} SKIP, {fail} FAIL / {len(results)} combos")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
