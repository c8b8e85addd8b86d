"""Roofline analysis from the dry-run artifacts (results/dryrun/*.json).

Per (arch × shape × mesh) cell, three terms in seconds, with the peaks
of the record's ``device_kind`` (``PEAKS``):

  compute    = FLOPs_global / (chips × peak bf16 FLOP/s)
  memory     = traffic_model_bytes / (chips × peak HBM B/s)
  collective = collective_bytes_per_device / peak B/s per link

A device kind missing from ``PEAKS`` is an error, not a default.

FLOPs_global comes from the jaxpr walker (scan-trip-count exact, includes
remat recompute); traffic from the documented analytic model; collective
bytes from the trip-count-aware HLO walk (per-device SPMD program, so no
chips division). MODEL_FLOPS = 6·N(_active)·D_tokens; the useful-compute
ratio MODEL_FLOPS / FLOPs_global exposes remat/dispatch waste.
"""
from __future__ import annotations

import glob
import json
import os

# per chip, keyed by jax's device_kind. TPU v5e: Google Cloud
# documentation "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# of inter-chip interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm": 819e9, "link": 50e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no roofline peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,          # one token per sequence
    "long_500k": 1,
}


def model_flops(rec: dict) -> float:
    n = rec["active_params"] if rec["arch"].find("moe") >= 0 or \
        rec["active_params"] != rec["params"] else rec["params"]
    tokens = SHAPE_TOKENS[rec["shape"]]
    mult = 6 if rec["shape"] == "train_4k" else 2
    return mult * n * tokens


def analyze(rec: dict) -> dict:
    peak = peaks(rec.get("device_kind", "unrecorded"))
    chips = 512 if rec["mesh"] == "2x16x16" else 256
    compute_s = rec["flops_global"] / (chips * peak["flops"])
    memory_s = rec.get("traffic_model_bytes", 0) / (chips * peak["hbm"])
    coll_s = rec["collectives"]["total_bytes"] / peak["link"]
    mf = model_flops(rec)
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", coll_s)), key=lambda kv: kv[1])[0]
    bound = max(compute_s, memory_s, coll_s)
    return {
        "cell": rec["cell"],
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / max(rec["flops_global"], 1),
        "roofline_fraction": compute_s / max(bound, 1e-30),
        "static_gb_per_dev": rec.get("static_arg_bytes_per_device", 0) / 2**30,
    }


def load_all(out_dir: str = "results/dryrun") -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") == "ok":
            out.append(analyze(rec))
        elif rec.get("status") == "skipped":
            out.append({"cell": rec["cell"], "skipped": rec["reason"][:60]})
    return out


def run():
    rows = load_all()
    if not rows:
        print("roofline,-1,no dryrun artifacts — run repro.launch.dryrun first")
        return
    for r in rows:
        if "skipped" in r:
            print(f"roofline_{r['cell']},0.0,skipped:{r['skipped']}")
            continue
        print(f"roofline_{r['cell']},0.0,"
              f"compute={r['compute_s']:.4f}s;memory={r['memory_s']:.4f}s;"
              f"collective={r['collective_s']:.4f}s;dom={r['dominant']};"
              f"useful={r['useful_ratio']:.2f};"
              f"roofline_frac={r['roofline_fraction']:.2f};"
              f"static_gb={r['static_gb_per_dev']:.1f}")


if __name__ == "__main__":
    run()
