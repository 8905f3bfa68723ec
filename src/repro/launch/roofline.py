"""Roofline analysis over the dry-run records (§Roofline deliverable).

Per (arch × shape) on the single-pod mesh, three terms in seconds-per-step
per chip, from the peaks of the chip the caller names (`CHIP_PEAKS`):

  compute    = HLO_FLOPs / bf16 peak
  memory     = HLO_bytes / HBM bandwidth
  collective = effective ICI bytes / per-link ICI bandwidth

plus MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE + attention term), the
useful-compute ratio MODEL_FLOPS/HLO_FLOPs, the dominant term, and the
roofline fraction = ideal-compute-time / bound-time.

SSM/hybrid cells get an analytic correction: the SSD chunk loop remains a
rolled `lax.scan` in the dry-run (XLA counts the body once), so its
(nc−1)/nc remainder is added back analytically (see DESIGN.md).

Usage: PYTHONPATH=src python -m repro.launch.roofline [--dir results/dryrun]
"""
from __future__ import annotations

import argparse
import json
import pathlib

from repro.configs import SHAPES, registry
from repro.configs.base import Family, ModelConfig, ShapeConfig

#: Published per-chip peaks, keyed by `jax.Device.device_kind`.
#: Source: Google Cloud documentation, "TPU v5e" (chip specifications:
#: 197 TFLOP/s bf16, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s interchip
#: interconnect over 4 links).
CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,    # FLOP/s
        "hbm_bytes": 16 * 2**30,
        "hbm_bw": 819e9,         # bytes/s
        "ici_link_bw": 50e9,     # bytes/s per link (1,600 Gbit/s / 4)
    },
}
V5E = "TPU v5 lite"
CHIPS = 256              # single pod


def chip_peaks(kind: str) -> dict:
    """Published peaks of the chip whose `device_kind` is `kind`. A chip
    missing from `CHIP_PEAKS` is an error, never a default."""
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None


def kernel_roofline(flops: float, hbm_bytes: float, kind: str) -> dict:
    """Roofline terms of one compiled function / kernel launch from its
    HLO cost analysis (`launch.hlo_stats.cost_stats`) on the chip
    `kind`: compute and memory seconds under its peaks, arithmetic
    intensity vs the machine balance, and which side bounds it: a
    lowering's FLOP/byte set against the chip it runs on."""
    peaks = chip_peaks(kind)
    compute_s = flops / peaks["bf16_flops"]
    memory_s = hbm_bytes / peaks["hbm_bw"]
    return {"flops": flops, "hbm_bytes": hbm_bytes,
            "compute_s": compute_s, "memory_s": memory_s,
            "intensity_flops_per_byte": flops / max(hbm_bytes, 1.0),
            "machine_balance": peaks["bf16_flops"] / peaks["hbm_bw"],
            "bound": "compute" if compute_s >= memory_s else "memory"}


def attn_flops(cfg: ModelConfig, shape: ShapeConfig, *, fwd_mult: float) -> float:
    """Global attention matmul FLOPs (QK^T + PV) for the step."""
    if cfg.family == Family.SSM:
        return 0.0
    B, S = shape.global_batch, shape.seq_len
    d_attn = cfg.n_heads * cfg.head_dim
    if cfg.family == Family.HYBRID:
        layers = cfg.n_layers // cfg.shared_attn_every
        d_attn = cfg.n_heads * cfg.head_dim
    elif cfg.family == Family.ENCDEC:
        layers = cfg.n_layers  # decoder self-attn; enc/cross added below
    else:
        layers = cfg.n_layers
    if shape.kind == "decode":
        ctx = min(cfg.swa_window or S, S)
        fl = 4 * layers * B * ctx * d_attn
        if cfg.family == Family.ENCDEC:
            fl += 4 * cfg.n_layers * B * cfg.encoder_seq * d_attn
        return fl * fwd_mult
    ctx_avg = S / 2 if not cfg.swa_window else min(cfg.swa_window, S / 2)
    fl = 4 * layers * B * S * ctx_avg * d_attn
    if cfg.family == Family.ENCDEC:
        enc = cfg.encoder_seq
        fl += 4 * cfg.n_encoder_layers * B * enc * enc * d_attn  # bidir enc
        fl += 4 * cfg.n_layers * B * S * enc * d_attn            # cross
    return fl * fwd_mult


def ssd_correction(cfg: ModelConfig, shape: ShapeConfig,
                   fwd_mult: float) -> float:
    """Analytic SSD chunk-loop FLOPs missing from the rolled scan: add back
    (nc-1)/nc of the total (the HLO counted one chunk)."""
    if cfg.family not in (Family.SSM, Family.HYBRID) or shape.kind == "decode":
        return 0.0
    s = cfg.ssm
    B, S = shape.global_batch, shape.seq_len
    Q = min(s.chunk_size, S)
    nc = max(S // Q, 1)
    if nc <= 1:
        return 0.0
    H = s.n_heads(cfg.d_model)
    P, N = s.head_dim, s.d_state
    per_chunk = B * (2 * Q * Q * N          # C·Bᵀ
                     + 2 * Q * Q * H * P    # w @ x
                     + 4 * Q * H * P * N)   # state update + y_inter
    total = per_chunk * nc * cfg.n_layers
    return total * (nc - 1) / nc * fwd_mult


def model_flops(cfg: ModelConfig, shape: ShapeConfig, remat: str) -> float:
    """Ideal useful FLOPs for the step (global)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        base = 6 * n_active * shape.tokens_per_step
        return base + attn_flops(cfg, shape, fwd_mult=3.0)
    mult = 1.0
    base = 2 * n_active * shape.tokens_per_step
    return base + attn_flops(cfg, shape, fwd_mult=mult)


def load(dirpath: pathlib.Path):
    recs = {}
    for f in dirpath.glob("*.json"):
        rec = json.loads(f.read_text())
        parts = f.stem.split("--")          # arch--shape--mesh[-tag]
        mesh_kind = ("multi_pod" if parts[2].startswith("multi")
                     else "single_pod")
        tag = parts[2].split("-", 1)[1] if "-" in parts[2] else "main"
        recs[(rec["arch"], rec["shape"], mesh_kind, tag)] = rec
    return recs


def analyse(recs, arch: str, shape_name: str, kind: str):
    peaks = chip_peaks(kind)
    cfg = registry.get_arch(arch)
    shape = SHAPES[shape_name]
    main = recs.get((arch, shape_name, "single_pod", "main"))
    mem_rec = recs.get((arch, shape_name, "single_pod", "mem")) or main
    extrapolated = False
    if main is None or main.get("status") != "ok":
        # heavy-cell fallback: reconstruct full-depth unrolled costs from the
        # l8 anchor + the rolled record — layer costs are exactly linear in L
        # (identical scanned layers): full = rolled + (L-1)·(l8 − rolled)/(l−1)
        l8 = recs.get((arch, shape_name, "single_pod", "l8"))
        rolled = recs.get((arch, shape_name, "single_pod", "mem"))
        if not (l8 and rolled and l8.get("status") == "ok"
                and rolled.get("status") == "ok"):
            return main and {"status": main.get("status", "missing"),
                             "reason": main.get("reason",
                                                main.get("error", ""))}
        lsmall = l8.get("layers_override", 8)
        L = cfg.n_layers

        def extra(get):
            body = (get(l8) - get(rolled)) / max(lsmall - 1, 1)
            return get(rolled) + (L - 1) * max(body, 0.0)

        main = {
            "status": "ok",
            "cost": {
                "flops": extra(lambda r: r["cost"]["flops"]),
                "bytes_accessed": extra(lambda r: r["cost"]["bytes_accessed"]),
            },
            "collectives": {"total": {"ici_bytes": extra(
                lambda r: r["collectives"]["total"]["ici_bytes"])}},
            "memory": rolled["memory"],
            "compile_s": l8.get("compile_s"),
        }
        mem_rec = rolled
        extrapolated = True

    flops_dev = main["cost"]["flops"]
    fwd_mult = 3.0 if shape.kind == "train" else 1.0
    flops_dev += ssd_correction(cfg, shape, fwd_mult) / CHIPS
    bytes_dev = main["cost"]["bytes_accessed"]
    ici_dev = main["collectives"]["total"]["ici_bytes"]

    compute_s = flops_dev / peaks["bf16_flops"]
    memory_s = bytes_dev / peaks["hbm_bw"]
    coll_s = ici_dev / peaks["ici_link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape, "block") / CHIPS
    ratio = mf / flops_dev if flops_dev else 0.0
    bound = max(terms.values())
    frac = (mf / peaks["bf16_flops"]) / bound if bound else 0.0
    peak_gib = (mem_rec["memory"]["peak_bytes"]
                if mem_rec.get("status") == "ok" else
                main["memory"]["peak_bytes"]) / 2 ** 30
    return {
        "status": "ok", "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops_dev": mf, "hlo_flops_dev": flops_dev,
        "useful_ratio": ratio, "roofline_fraction": frac,
        "peak_gib": peak_gib,
        "fits_hbm": peak_gib * 2 ** 30 <= peaks["hbm_bytes"],
        "compile_s": main.get("compile_s"),
        "extrapolated": extrapolated,
    }


def table(kind: str, dirpath: str = "results/dryrun") -> str:
    recs = load(pathlib.Path(dirpath))
    lines = ["| arch | shape | compute s | memory s | coll s | dominant | "
             "MODEL/HLO | roofline frac | peak GiB |",
             "|---|---|---|---|---|---|---|---|---|"]
    for arch, shape_name, runs, why in registry.all_cells():
        if not runs:
            lines.append(f"| {arch} | {shape_name} | — | — | — | skipped | "
                         f"— | — | — |")
            continue
        a = analyse(recs, arch, shape_name, kind)
        if not a or a.get("status") != "ok":
            lines.append(f"| {arch} | {shape_name} | ? | ? | ? | "
                         f"{(a or {}).get('status')} | ? | ? | ? |")
            continue
        lines.append(
            f"| {arch} | {shape_name} | {a['compute_s']:.4f} | "
            f"{a['memory_s']:.4f} | {a['collective_s']:.4f} | "
            f"{a['dominant']} | {a['useful_ratio']:.2f} | "
            f"{a['roofline_fraction']:.3f} | {a['peak_gib']:.1f}"
            f"{'' if a['fits_hbm'] else ' ⚠'} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    # the dry-run's single-pod mesh models a v5e pod
    if args.json:
        recs = load(pathlib.Path(args.dir))
        out = {}
        for arch, shape_name, runs, _ in registry.all_cells():
            if runs:
                out[f"{arch}/{shape_name}"] = analyse(recs, arch,
                                                      shape_name, V5E)
        print(json.dumps(out, indent=1, default=str))
    else:
        print(table(V5E, args.dir))


if __name__ == "__main__":
    main()
