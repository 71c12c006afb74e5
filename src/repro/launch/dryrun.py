"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

:func:`main` forces 512 placeholder host devices (``XLA_FLAGS``) before
anything initializes a jax backend — jax locks the device count at first
initialization, and the production mesh needs them. Importing this
module changes no global state.

Per cell this produces (and prints):
  * compiled.memory_analysis()  — proves the per-device footprint fits;
  * compiled.cost_analysis()    — per-device HLO FLOPs / bytes for the
                                  roofline (§Roofline reads these);
  * collective byte totals parsed from the compiled HLO text, per
    collective kind (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape train_4k [--multi-pod] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import os
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# --------------------------------------------------------------------------
# HLO parsing: collective bytes
# --------------------------------------------------------------------------

_ARRAY_RE = re.compile(r"([a-z]+[0-9]+(?:e[0-9]+m[0-9]+(?:fn)?)?)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s+([^=]+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _ARRAY_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Result-bytes per collective kind from compiled HLO (per device).

    Convention: we sum RESULT sizes (for all-gather this is the gathered
    size, an upper bound on wire bytes per device; for reduce-scatter the
    scattered size, a lower bound; all-reduce wire bytes ~= 2x result in
    ring terms — reported raw here, the roofline applies the ring factor).
    `-done` ops alias their `-start` and are not counted.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        out[kind] += _type_bytes(type_str)
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


# --------------------------------------------------------------------------
# cell construction
# --------------------------------------------------------------------------

def _lower_lm_cell(arch: str, shape_name: str, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import RunConfig, get_config, get_shape
    from repro.models import build_model
    from repro.models.pshint import activation_policy
    from repro.launch import sharding as shd
    from repro.launch.train import TrainState, make_train_step
    from repro.optim import adamw_init

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    model = build_model(cfg)

    if shape.kind == "decode" and shape.seq_len >= 100_000 and \
            not cfg.sub_quadratic:
        return None, {"status": "skipped",
                      "reason": "full attention at 512k decode "
                                "(DESIGN.md §5)"}

    aparams = jax.eval_shape(lambda: model.init(0))
    pspecs = shd.param_specs(aparams, mesh)

    def nshard(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))

    # sequence-parallel activation policy (train/prefill only)
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    batch_axes = shd._batch_axes(mesh, shape.global_batch)
    def make_policy(batch_dim: int):
        """Megatron-style layout policy: SP residuals + TP ffn hidden.

        No "heads" constraint: measured on qwen1.5-110b it forces
        involuntary resharding copies inside the flash-attention scan
        (+1.7 GB/dev) — see EXPERIMENTS.md §Perf iteration log.
        """
        bx = shd._batch_axes(mesh, batch_dim)
        pol = {
            # MLP hidden: ff sharded over model (column-parallel)
            "ffn": NamedSharding(mesh, P(bx, None, "model")),
        }
        if shape.kind != "decode" and shape.seq_len % msize == 0:
            pol["residual"] = NamedSharding(mesh, P(bx, "model", None))
        return pol

    batch_axes = shd._batch_axes(mesh, shape.global_batch)
    policy = make_policy(shape.global_batch)

    if shape.kind == "train":
        # Microbatch gradient accumulation (O5 at the gradient buffer):
        # 8 microbatches divide the per-step activation live-set 8x and
        # keep the cross-replica reduction at once-per-step (measured:
        # 23.8 -> 12.9 GB/dev on qwen1.5-110b, §Perf).
        n_micro = 8 if shape.global_batch % (8 * 8) == 0 else 1
        micro = shape.global_batch // n_micro
        specs = model.input_specs(shape)
        batch_like = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                (n_micro, micro) + s.shape[1:], s.dtype), specs)
        astate = TrainState(params=aparams,
                            opt=jax.eval_shape(adamw_init, aparams))
        ospecs = shd.optimizer_specs(pspecs)
        mb_axes = shd._batch_axes(mesh, micro)
        bspecs = jax.tree_util.tree_map(
            lambda s: P(None, mb_axes, *([None] * (len(s.shape) - 2))),
            batch_like)
        state_sh = TrainState(params=nshard(pspecs), opt=nshard(ospecs))
        step = make_train_step(model, RunConfig(microbatch=n_micro),
                               total_steps=1000)
        # residual/hidden activations are (micro, S, d) under accumulation
        policy = make_policy(micro)
        with activation_policy(policy):
            # donate the train state: params/opt buffers alias in->out
            jf = jax.jit(step, in_shardings=(state_sh, nshard(bspecs)),
                         donate_argnums=(0,))
            lowered = jf.lower(astate, batch_like)
        return lowered, {"kind": "train", "n_micro": n_micro}

    if shape.kind == "prefill":
        specs = model.input_specs(shape)
        bspecs = shd.batch_specs(specs, mesh)

        def prefill_step(params, batch):
            return model.prefill(params, batch, shape.seq_len)

        with activation_policy(policy):
            jf = jax.jit(prefill_step,
                         in_shardings=(nshard(pspecs), nshard(bspecs)))
            lowered = jf.lower(aparams, specs)
        return lowered, {"kind": "prefill"}

    # decode: one new token against a seq_len-deep cache
    B = shape.global_batch
    cache_like = jax.eval_shape(
        lambda: model.init_decode_state(B, shape.seq_len))
    cspecs = shd.cache_specs(cache_like, mesh, cfg)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = NamedSharding(mesh, P(shd._batch_axes(mesh, B), None))
    pos_sh = NamedSharding(mesh, P())

    def decode_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)

    # donate the cache: the multi-GB KV buffers alias in->out (§Perf)
    jf = jax.jit(decode_step,
                 in_shardings=(nshard(pspecs), nshard(cspecs), tok_sh,
                               pos_sh), donate_argnums=(1,))
    lowered = jf.lower(aparams, cache_like, tok,
                       jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, {"kind": "decode"}


# --------------------------------------------------------------------------
# cell runner
# --------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str | None = None, verbose: bool = True) -> dict:
    from repro.launch.mesh import make_production_mesh

    t0 = time.time()
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    n_chips = 512 if multi_pod else 256
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": n_chips}
    hlo_text = None
    try:
        lowered, info = _lower_lm_cell(arch, shape_name, mesh)
        rec.update(info)
        if lowered is None:           # skipped cell
            rec["status"] = rec.get("status", "skipped")
        else:
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            hlo_text = hlo
            coll = collective_bytes(hlo)
            # Loop-aware walk: XLA cost_analysis counts while bodies ONCE
            # (a scanned layer stack under-reports ~n_layers x); this
            # multiplies through scan trip counts. See hlo_cost.py.
            from repro.launch import hlo_cost
            la = hlo_cost.analyze(hlo)
            rec.update({
                "status": "ok",
                "compile_s": round(time.time() - t0, 1),
                "memory": {
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "alias_bytes": ma.alias_size_in_bytes,
                    "peak_est_bytes": ma.argument_size_in_bytes
                    + ma.temp_size_in_bytes + ma.output_size_in_bytes
                    - ma.alias_size_in_bytes,
                },
                "cost": {
                    "flops_per_device": la["flops"],
                    "bytes_per_device": la["bytes"],
                    "transcendentals": la["trans"],
                    "xla_flops_loop_body_once": ca.get("flops", 0.0),
                    "xla_bytes_loop_body_once": ca.get("bytes accessed",
                                                       0.0),
                },
                "collectives": {
                    "bytes": la["coll"],
                    "counts": la["coll_counts"],
                    "total_bytes": sum(la["coll"].values()),
                    "body_once_bytes": coll["bytes"],
                },
            })
            if verbose:
                print(f"[{arch} x {shape_name} x {mesh_name}] "
                      f"compile {rec['compile_s']}s")
                print("  memory_analysis:", ma)
                print(f"  cost(loop-aware): flops/dev={la['flops']:.3e} "
                      f"bytes/dev={la['bytes']:.3e}")
                print(f"  collectives: "
                      f"{ {k: int(v) for k, v in la['coll_counts'].items() if v} } "
                      f"total {sum(la['coll'].values())/1e6:.1f} MB/dev")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] FAILED: "
                  f"{rec['error']}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir,
                          f"{arch}__{shape_name}__{mesh_name}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
        if rec["status"] == "ok" and hlo_text is not None:
            import gzip
            with gzip.open(fn.replace(".json", ".hlo.gz"), "wt") as f:
                f.write(hlo_text)
    return rec


def reanalyze(out_dir: str) -> int:
    """Recompute cost/collective fields from saved .hlo.gz artifacts
    (no recompilation) after hlo_cost model changes."""
    import glob
    import gzip

    from repro.launch import hlo_cost
    n = 0
    for fn in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        gz = fn.replace(".json", ".hlo.gz")
        if not os.path.exists(gz):
            continue
        with open(fn) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        with gzip.open(gz, "rt") as f:
            hlo = f.read()
        la = hlo_cost.analyze(hlo)
        rec["cost"]["flops_per_device"] = la["flops"]
        rec["cost"]["bytes_per_device"] = la["bytes"]
        rec["cost"]["transcendentals"] = la["trans"]
        rec["collectives"]["bytes"] = la["coll"]
        rec["collectives"]["counts"] = la["coll_counts"]
        rec["collectives"]["total_bytes"] = sum(la["coll"].values())
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
        n += 1
    return n


LM_SHAPE_NAMES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")


def force_host_devices(n: int = 512) -> None:
    """Ask the CPU backend for ``n`` placeholder devices. Effective only
    before the first jax backend initialization in this process."""
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute cost fields from saved .hlo.gz")
    args = ap.parse_args()

    if args.reanalyze:
        n = reanalyze(args.out)
        print(f"reanalyzed {n} cells")
        sys.exit(0)

    from repro.configs import list_archs

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in LM_SHAPE_NAMES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for multi_pod in meshes:
        for arch, shape in cells:
            mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
            out_fn = os.path.join(
                args.out, f"{arch}__{shape}__{mesh_name}.json")
            if args.skip_existing and os.path.exists(out_fn):
                with open(out_fn) as f:
                    prev = json.load(f)
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[{arch} x {shape} x {mesh_name}] cached "
                          f"({prev['status']})")
                    continue
            rec = run_cell(arch, shape, multi_pod=multi_pod,
                           out_dir=args.out)
            if rec["status"] == "error":
                failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
