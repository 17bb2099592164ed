"""Roofline derivation from the dry-run artifacts (EXPERIMENTS.md §Roofline).

    t_compute    = HLO_FLOPs / peak bf16 FLOP/s
    t_memory     = HLO_bytes / peak HBM bytes/s
    t_collective = collective_bytes / peak ICI bytes/s

with the per-chip peaks of the record's ``device_kind`` (:data:`PEAKS`).

``cost_analysis()`` numbers from the host-CPU dry-run are per-*device*
programs, so `chips` is already factored out of flops/bytes; collective bytes
are summed over the per-device HLO (payload crossing this chip's links).

MODEL_FLOPS = 6·N·D (dense train) / 6·N_active·D (MoE) / 2·N·D (inference),
giving the useful-compute ratio that flags remat/dispatch overhead.

CPU-backend caveat (documented): XLA-CPU promotes bf16 dot operands to f32,
inflating `bytes accessed` vs a TPU executable; the memory term is therefore
an upper bound. FLOPs and collective bytes are layout-faithful.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Optional

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 819 GB/s HBM, 1,600 Gbit/s inter-chip interconnect).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes": 819e9,
                    "ici_bytes": 1600e9 / 8},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


# tokens (or equivalent work items) per step, for MODEL_FLOPS
def model_flops(arch_name: str, shape: str, variant: str = "base") -> Optional[float]:
    from repro.configs import registry

    arch = registry.get(arch_name)
    if arch.family == "lm":
        cfg = arch.model
        n_active = cfg.active_param_count()
        if shape == "train_4k":
            return 6.0 * n_active * 256 * 4096
        if shape == "prefill_32k":
            return 2.0 * n_active * 32 * 32768
        if shape == "decode_32k":
            return 2.0 * n_active * 128  # one token per sequence
        if shape == "long_500k":
            return 2.0 * n_active * 1
    if arch.family == "gnn":
        cfg = arch.model
        d = arch.shape(shape).dims
        n_edges = d.get("n_edges", d.get("pad_edges", 0)) or d.get("batch", 1) * d.get("n_edges", 0)
        # per layer: 5 node GEMMs (N·h²) + edge ops (E·h); fwd+bwd ≈ 3×
        n_nodes = d.get("n_nodes", d.get("pad_nodes", 0))
        if shape == "molecule":
            n_nodes, n_edges = d["batch"] * d["n_nodes"], d["batch"] * d["n_edges"]
        per_layer = 2 * (5 * n_nodes * cfg.d_hidden**2 + 6 * n_edges * cfg.d_hidden)
        return 3.0 * cfg.n_layers * per_layer
    if arch.family == "recsys":
        return None  # embedding-lookup dominated; flops not the right lens
    if arch.family == "cf":
        d = arch.shape(shape).dims
        u, p = d["n_users"], d["n_items"]
        n = d.get("n_landmarks", arch.model.n_landmarks)
        if "fit" in shape:
            return 2.0 * u * n * p + 2.0 * u * u * n  # the paper's complexity
        return None
    return None


_CAL_PATH = Path("exp/calibration.json")


def _calibration() -> Dict:
    if _CAL_PATH.exists():
        return json.loads(_CAL_PATH.read_text())
    return {}


def derive(record: Dict, calibration: Optional[Dict] = None) -> Dict:
    """record: one dry-run JSON entry → roofline terms (seconds).

    When a trip-count calibration exists for the cell (benchmarks.calibrate),
    its extrapolated flops/bytes/collectives replace the raw numbers (XLA cost
    analysis counts while-loop bodies once — see calibrate.py)."""
    calibration = _calibration() if calibration is None else calibration
    key = f"{record['arch']}/{record['shape']}/{record.get('variant', 'base')}"
    cal = calibration.get(key)
    if cal:
        coll = {k[5:]: max(v, 0.0) for k, v in cal.items() if k.startswith("coll_")}
        flops = max(cal["flops"], 0.0)
        bytes_acc = max(cal["bytes"], 0.0)
    else:
        coll = {k: v for k, v in record["collectives"].items() if not k.startswith("_")}
        flops = max(record["flops"], 0.0)
        bytes_acc = max(record["bytes_accessed"], 0.0)
    coll_bytes = sum(coll.values())
    peak = peaks(record["device_kind"])
    t_c = flops / peak["bf16_flops"]
    t_m = bytes_acc / peak["hbm_bytes"]
    t_n = coll_bytes / peak["ici_bytes"]
    dominant = max((t_c, "compute"), (t_m, "memory"), (t_n, "collective"))[1]
    mf = model_flops(record["arch"], record["shape"], record.get("variant", "base"))
    chips = record["n_devices"]
    useful = (mf / (flops * chips)) if (mf and flops > 0) else None
    if useful is not None:
        useful = min(useful, 99.0)
    bound = max(t_c, t_m, t_n)
    return {
        **{k: record[k] for k in ("arch", "shape", "variant", "mesh")},
        "t_compute_s": t_c,
        "t_memory_s": t_m,
        "t_collective_s": t_n,
        "dominant": dominant,
        "model_flops": mf,
        "useful_compute_ratio": useful,
        "roofline_fraction": (t_c / bound) if bound > 0 else None,
        "collective_detail": coll,
        "calibrated": bool(cal),
    }


def table(path: str = "exp/dryrun_singlepod.json") -> list:
    records = json.loads(Path(path).read_text())
    cal = _calibration()
    return [derive(r, cal) for r in records]


def render(rows: list) -> str:
    hdr = (f"{'arch':18s} {'shape':14s} {'var':9s} {'t_comp':>9s} {'t_mem':>9s} "
           f"{'t_coll':>9s} {'dominant':>10s} {'useful':>7s} {'roofline':>8s}")
    out = [hdr, "-" * len(hdr)]
    for r in rows:
        u = f"{r['useful_compute_ratio']:.2f}" if r["useful_compute_ratio"] else "  -"
        rf = f"{r['roofline_fraction']:.2f}" if r["roofline_fraction"] is not None else "  -"
        out.append(
            f"{r['arch']:18s} {r['shape']:14s} {r['variant']:9s} "
            f"{r['t_compute_s']:9.2e} {r['t_memory_s']:9.2e} {r['t_collective_s']:9.2e} "
            f"{r['dominant']:>10s} {u:>7s} {rf:>8s}"
        )
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    path = sys.argv[1] if len(sys.argv) > 1 else "exp/dryrun_singlepod.json"
    print(render(table(path)))
