import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and record memory/cost/collective analysis.

The two lines above MUST stay first: jax locks the device count on first
initialization, and the dry-run needs 512 placeholder host devices. Nothing
else in the repo sets this flag (smoke tests and benchmarks see 1 device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_configs
from repro.configs.base import ArchConfig, ShapeCfg
from repro.distributed import sharding as SH
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.optim.optimizers import OptConfig, apply_updates, init_opt_state

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

ARCHS = [
    "smollm-360m", "gemma-2b", "chatglm3-6b", "mistral-large-123b",
    "mamba2-130m", "grok-1-314b", "arctic-480b", "whisper-small",
    "recurrentgemma-9b", "internvl2-76b",
]


def vocab_pad_for(cfg: ArchConfig, mesh) -> int:
    m = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    return m if cfg.vocab % m else 1


def default_microbatches(cfg: ArchConfig) -> int:
    """Gradient-accumulation factor sized to the per-device activation
    budget (see EXPERIMENTS.md §Perf for the derivation)."""
    if cfg.d_model >= 8192:
        return 8
    if cfg.d_model >= 6144 or cfg.family == "moe":
        return 4
    if cfg.d_model >= 4096:
        return 2
    return 1


def with_mesh_context(cfg: ArchConfig, mesh) -> ArchConfig:
    """Attach the distribution context (tp size, activation constraints)."""
    axes = tuple(zip(mesh.axis_names, mesh.devices.shape))
    tp = dict(axes).get("model", 1)
    # cost probes (unroll_loops=True) must stay single-pass: the grad-
    # accumulation scan is a while loop whose body cost_analysis counts once
    mb = 1 if cfg.unroll_loops else default_microbatches(cfg)
    return dataclasses.replace(cfg, tp_size=tp, shard_acts=True,
                               mesh_axes=axes, microbatches=mb)


def build_lowering(cfg: ArchConfig, shape: ShapeCfg, mesh, opt=OptConfig()):
    """Returns a jax.stages.Lowered for the cell's entry point."""
    with mesh:
        return _build_lowering_inner(cfg, shape, mesh, opt)


def _build_lowering_inner(cfg: ArchConfig, shape: ShapeCfg, mesh, opt):
    cfg = with_mesh_context(cfg, mesh)
    pad = vocab_pad_for(cfg, mesh)
    pspec = api.param_spec(cfg, pad)
    p_sh = SH.params_pspecs_cfg(pspec, mesh, cfg)
    in_specs = api.input_specs(cfg, shape)
    d_sh = SH.data_pspecs(in_specs, mesh, cfg)
    ba = SH.batch_axes(mesh)
    b_ok = shape.global_batch  # batch spec computed inside data_pspecs

    if shape.kind == "train":
        o_spec = jax.eval_shape(lambda p: init_opt_state(p, opt), pspec)
        o_sh = jax.tree.map(lambda _: P(), o_spec)
        # optimizer state sharded like its parameter
        o_sh = o_sh._replace(m=p_sh, v=None if o_spec.v is None else p_sh,
                             step=P())

        M = cfg.microbatches

        def train_step(params, opt_state, batch):
            if M <= 1:
                loss, grads = jax.value_and_grad(
                    lambda p: api.loss_fn(p, batch, cfg))(params)
            else:
                # gradient accumulation: M sequential microbatches; the
                # per-microbatch activation footprint shrinks by M
                mb = jax.tree.map(
                    lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]),
                    batch)
                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def acc(carry, mbatch):
                    l_sum, g_sum = carry
                    l, g = jax.value_and_grad(
                        lambda p: api.loss_fn(p, mbatch, cfg))(params)
                    g_sum = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), g_sum, g)
                    return (l_sum + l, g_sum), None

                (loss, grads), _ = jax.lax.scan(acc, (jnp.zeros(()), g0), mb)
                loss = loss / M
                grads = jax.tree.map(lambda g: g / M, grads)
            params, opt_state = apply_updates(params, grads, opt_state, opt)
            return params, opt_state, loss

        fn = jax.jit(
            train_step,
            in_shardings=(SH.to_named(p_sh, mesh), SH.to_named(o_sh, mesh),
                          SH.to_named(d_sh, mesh)),
            out_shardings=(SH.to_named(p_sh, mesh), SH.to_named(o_sh, mesh),
                           NamedSharding(mesh, P())),
        )
        return fn.lower(pspec, o_spec, in_specs)

    if shape.kind == "prefill":
        cache_shape = jax.eval_shape(
            lambda p, b: api.prefill(p, b, cfg), pspec, in_specs)[1]
        c_sh = SH.cache_pspecs(cache_shape, mesh, cfg)
        logits_sh = P(None, None, "model")

        def prefill_step(params, batch):
            return api.prefill(params, batch, cfg)

        fn = jax.jit(
            prefill_step,
            in_shardings=(SH.to_named(p_sh, mesh), SH.to_named(d_sh, mesh)),
            out_shardings=(NamedSharding(mesh, logits_sh),
                           SH.to_named(c_sh, mesh)),
        )
        return fn.lower(pspec, in_specs)

    # decode
    c_spec = in_specs["caches"]
    c_sh = SH.cache_pspecs(c_spec, mesh, cfg)
    tok_sh = SH.data_pspecs({"token": in_specs["token"],
                             "pos": in_specs["pos"]}, mesh, cfg)
    logits_sh = P(None, "model")

    def serve_step(params, caches, token, pos):
        return api.decode_step(params, caches, {"token": token, "pos": pos}, cfg)

    fn = jax.jit(
        serve_step,
        in_shardings=(SH.to_named(p_sh, mesh), SH.to_named(c_sh, mesh),
                      NamedSharding(mesh, tok_sh["token"]),
                      NamedSharding(mesh, tok_sh["pos"])),
        out_shardings=(NamedSharding(mesh, logits_sh), SH.to_named(c_sh, mesh)),
    )
    return fn.lower(pspec, c_spec, in_specs["token"], in_specs["pos"])


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, hlo_dump: bool = False,
             verbose: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "status": "skipped", "reason": None}
    if not cfg.supports(shape):
        rec["reason"] = ("long_500k skipped: pure full-attention arch "
                         "(assignment spec; see DESIGN.md §Arch-applicability)")
        _save(rec, save)
        return rec
    if cfg.family == "encdec" and shape.kind == "decode" and shape_name == "long_500k":
        rec["reason"] = "enc-dec long-context decode N/A"
        _save(rec, save)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        lowered = build_lowering(cfg, shape, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if verbose:  # assignment-literal dump: proves it fits + flops/bytes
            print(mem)
            print({k: cost.get(k) for k in
                   ("flops", "bytes accessed", "transcendentals")})
        hlo = compiled.as_text()
        coll = analyze_collectives(hlo)
        n_dev = mesh.devices.size
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                code_bytes=mem.generated_code_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                per_device_total=(mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
            ),
            cost=dict(
                flops=cost.get("flops", -1),
                bytes_accessed=cost.get("bytes accessed", -1),
                transcendentals=cost.get("transcendentals", -1),
            ),
            collectives=coll,
            n_devices=n_dev,
        )
        if hlo_dump:
            (ART_DIR / f"{arch}__{shape_name}__{mesh_name}.hlo.txt").write_text(hlo)
        print(f"[ok] {arch} {shape_name} {mesh_name}: compile {t_compile:.0f}s, "
              f"temp/dev {mem.temp_size_in_bytes/2**30:.2f} GiB, "
              f"coll {coll['total_bytes']/2**30:.2f} GiB")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", reason=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[FAIL] {arch} {shape_name} {mesh_name}: {e}")
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool):
    if not save:
        return
    ART_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    (ART_DIR / name).write_text(json.dumps(rec, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--hlo-dump", action="store_true")
    ap.add_argument("--verbose", action="store_true",
                    help="print raw memory_analysis()/cost_analysis()")
    args = ap.parse_args()

    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    if args.multi_pod or not args.single_pod:
        meshes.append(True)

    cells = []
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))
    ok = fail = skip = 0
    for a, s, mp in cells:
        rec = run_cell(a, s, mp, hlo_dump=args.hlo_dump, verbose=args.verbose)
        ok += rec["status"] == "ok"
        fail += rec["status"] == "error"
        skip += rec["status"] == "skipped"
    print(f"\ndry-run summary: {ok} ok, {fail} failed, {skip} skipped "
          f"of {len(cells)} cells")
    return 0 if fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
