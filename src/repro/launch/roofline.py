import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Roofline analysis per (architecture × shape) on the single-pod mesh.

Methodology (CPU container — TPU v5e is the *target*):
  * ``compiled.cost_analysis()`` counts a `while` body ONCE regardless of
    trip count, so raw numbers from the scan-over-layers compile undercount
    by ~n_layers. We therefore compile *unrolled cost probes*: the same cell
    at 1 period and 2 periods of layers with python-loop (exact, statically
    causal-skipped) attention, and difference them:

        per_period = C(2p) − C(1p);   base = C(1p) − per_period
        total      = base + n_periods·per_period (+ tail probe if any)

    This yields exact per-device HLO FLOPs, bytes and collective bytes
    (collectives parsed from the probe HLO text, which has no loops).
  * The full-graph compile from the dry-run supplies the memory-fit numbers
    and a trip-count-weighted collective cross-check.

Terms (per device == per chip, SPMD):
    compute   = flops / 197e12        (bf16 peak per v5e chip)
    memory    = bytes / 819e9         (HBM bw per chip)
    collective= coll_bytes / 50e9     (ICI per chip)
    MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE), D = tokens/step.

Usage:
  PYTHONPATH=src python -m repro.launch.roofline --all
  PYTHONPATH=src python -m repro.launch.roofline --arch gemma-2b --shape train_4k
Artifacts: experiments/roofline/<arch>__<shape>.json + markdown table.
"""
import argparse
import dataclasses
import json
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.base import ArchConfig, ShapeCfg
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import HW, make_production_mesh
from repro.models import api
from repro.models.module import count_params
from repro.models.transformer import period_len, split_plan

ART = Path(__file__).resolve().parents[3] / "experiments" / "roofline"
DRY = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

ARCHS = [
    "smollm-360m", "gemma-2b", "chatglm3-6b", "mistral-large-123b",
    "mamba2-130m", "grok-1-314b", "arctic-480b", "whisper-small",
    "recurrentgemma-9b", "internvl2-76b",
]


# ---------------------------------------------------------------------------
# Cost probes
# ---------------------------------------------------------------------------
def _probe_cfg(cfg: ArchConfig, n_layers: int, shape: ShapeCfg) -> ArchConfig:
    # remat stays ON for train probes: the deployed plan recomputes the
    # forward in the backward (~1.33x flops) and the roofline must count it
    return dataclasses.replace(
        cfg, n_layers=n_layers,
        n_enc_layers=min(cfg.n_enc_layers, n_layers),
        scan_layers=False, unroll_loops=True,
        attn_chunk=min(4096, shape.seq_len))


def _compile_costs(cfg: ArchConfig, shape: ShapeCfg, mesh) -> Dict[str, float]:
    from repro.launch.dryrun import build_lowering
    lowered = build_lowering(cfg, shape, mesh)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    coll = analyze_collectives(compiled.as_text())
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)),
                coll=float(coll["total_bytes"]),
                coll_per_kind={k: float(v) for k, v in coll["per_kind"].items()})


def probe_costs(arch: str, shape_name: str) -> Dict[str, float]:
    """Exact per-device totals extrapolated from unrolled 1p/2p probes."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    if cfg.family == "encdec":
        per, n_full, tail = 1, cfg.n_layers, []
    else:
        per = period_len(cfg)
        _, n_full, tail = split_plan(cfg)

    c1 = _compile_costs(_probe_cfg(cfg, per, shape), shape, mesh)
    c2 = _compile_costs(_probe_cfg(cfg, 2 * per, shape), shape, mesh)
    out: Dict[str, float] = {}
    for k in ("flops", "bytes", "coll"):
        per_period = c2[k] - c1[k]
        base = c1[k] - per_period
        total = base + n_full * per_period
        out[k + "_per_period"] = per_period
        out[k + "_base"] = base
        out[k] = total
    if tail:
        c_tail = _compile_costs(_probe_cfg(cfg, per + len(tail), shape),
                                shape, mesh)
        for k in ("flops", "bytes", "coll"):
            out[k] += c_tail[k] - c1[k]
    # whisper: encoder scales with n_enc_layers too; the probe pairs scale
    # BOTH stacks 1->2, so per_period already covers (enc+dec) jointly and
    # n_full extrapolation is exact because n_enc_layers == n_layers.
    out["per_kind_2p"] = c2["coll_per_kind"]
    return out


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS
# ---------------------------------------------------------------------------
def model_flops(cfg: ArchConfig, shape: ShapeCfg) -> Tuple[float, float]:
    """(6·N(_active)·D_total, N_active). Decode: D = B tokens per step."""
    pspec = api.param_spec(cfg)
    n_total = count_params(pspec)
    n_active = n_total
    if cfg.family == "moe":
        # per-expert FFN params counted at top_k/E utilization
        per_expert = 3 * cfg.d_model * cfg.d_ff
        expert_total = cfg.n_layers * cfg.n_experts * per_expert
        n_active = n_total - expert_total + cfg.n_layers * cfg.top_k * per_expert
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        factor = 6.0
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        factor = 2.0
    else:  # decode: one token per sequence per step
        tokens = shape.global_batch
        factor = 2.0
        # decode compute excludes the embedding table (gather) but we keep
        # 2·N·B as the standard approximation
    return factor * n_active * tokens, float(n_active)


# ---------------------------------------------------------------------------
# Assemble the roofline record
# ---------------------------------------------------------------------------
def analyze_cell(arch: str, shape_name: str, *, use_probes: bool = True
                 ) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "status": "skipped"}
    if not cfg.supports(shape):
        rec["reason"] = "long_500k N/A for full-attention arch"
        return rec
    n_chips = 256
    dry_path = DRY / f"{arch}__{shape_name}__pod_16x16.json"
    dry = json.loads(dry_path.read_text()) if dry_path.exists() else None

    if use_probes:
        costs = probe_costs(arch, shape_name)
    else:
        costs = dict(flops=dry["cost"]["flops"],
                     bytes=dry["cost"]["bytes_accessed"],
                     coll=dry["collectives"]["total_bytes"])

    t_compute = costs["flops"] / HW["peak_flops_bf16"]
    t_memory = costs["bytes"] / HW["hbm_bw"]
    t_coll = costs["coll"] / HW["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    mf, n_active = model_flops(cfg, shape)
    mf_per_chip = mf / n_chips
    useful_ratio = mf_per_chip / max(costs["flops"], 1.0)
    bound = max(terms.values())
    # achievable step time = max(terms); roofline fraction of the dominant
    # resource = share of the bound spent on *useful* model flops
    roofline_fraction = (mf_per_chip / HW["peak_flops_bf16"]) / bound if bound else 0.0

    rec.update(
        status="ok",
        per_device=costs,
        terms_s=terms,
        dominant=dominant,
        model_flops_total=mf,
        n_active_params=n_active,
        model_flops_per_chip=mf_per_chip,
        useful_flops_ratio=useful_ratio,
        roofline_fraction=roofline_fraction,
        memory_fit=None if dry is None else dry["memory"],
        full_graph_collectives=None if dry is None else dry["collectives"]["per_kind"],
    )
    return rec


def improvement_note(rec: dict) -> str:
    d = rec["dominant"]
    if d == "compute_s":
        return ("compute-bound: reduce non-useful FLOPs (attention block "
                "skipping, fused kernels) or grow per-chip batch")
    if d == "memory_s":
        return ("HBM-bound: fuse elementwise chains, shrink remat traffic, "
                "quantize caches/weights")
    return ("collective-bound: reshard to cut all-gathers (wider FSDP "
            "prefetch overlap, SP off for short seqs), compress grads")


def write_markdown(records, path: Path):
    lines = ["| arch | shape | compute s | memory s | collective s | "
             "dominant | MODEL_FLOPs/HLO | roofline frac | note |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in records:
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — | — "
                         f"| {r.get('reason','skip')} |")
            continue
        t = r["terms_s"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.3e} | "
            f"{t['memory_s']:.3e} | {t['collective_s']:.3e} | "
            f"{r['dominant'].replace('_s','')} | "
            f"{r['useful_flops_ratio']:.2f} | {r['roofline_fraction']:.2%} | "
            f"{improvement_note(r)[:60]} |")
    path.write_text("\n".join(lines))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    args = ap.parse_args()
    ART.mkdir(parents=True, exist_ok=True)
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    records = []
    for a in archs:
        for s in shapes:
            try:
                rec = analyze_cell(a, s, use_probes=not args.no_probes)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": a, "shape": s, "status": "error",
                       "reason": f"{type(e).__name__}: {e}"}
            records.append(rec)
            (ART / f"{a}__{s}.json").write_text(
                json.dumps(rec, indent=1, default=str))
            if rec["status"] == "ok":
                t = rec["terms_s"]
                print(f"[{a} {s}] comp {t['compute_s']:.2e}s mem "
                      f"{t['memory_s']:.2e}s coll {t['collective_s']:.2e}s "
                      f"-> {rec['dominant']} useful={rec['useful_flops_ratio']:.2f} "
                      f"roofline={rec['roofline_fraction']:.1%}")
            else:
                print(f"[{a} {s}] {rec['status']}: {rec.get('reason','')[:120]}")
    write_markdown(records, ART / "roofline_table.md")


if __name__ == "__main__":
    main()
