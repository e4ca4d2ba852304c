"""Roofline report: aggregate dry-run JSONs into the §Roofline table.

Usage:
    PYTHONPATH=src python -m repro.launch.roofline --dir results/dryrun \
        [--format md|csv]
"""
from __future__ import annotations

import argparse
import glob
import json
import os


def load_records(d: str):
    recs = []
    for f in sorted(glob.glob(os.path.join(d, "*.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def fmt_s(x):
    return f"{x:.3e}" if x is not None else "-"


def one_liner(rec) -> str:
    """What would move the dominant term down."""
    if rec.get("status") != "ok":
        return ""
    r = rec["roofline"]
    dom = r["dominant"]
    shape = rec["shape"]
    hints = {
        ("memory_s", "train"): "bf16 intermediates + fewer remat round-trips",
        ("memory_s", "prefill"): "fused (Pallas) attention keeps tiles in VMEM",
        ("memory_s", "decode"): "quantized / windowed KV cache shrinks the stream",
        ("compute_s", "train"): "drop remat recompute (more HBM) or pack MXU tiles",
        ("compute_s", "prefill"): "skip fully-masked window blocks",
        ("compute_s", "decode"): "batch decode steps (speculative/multi-token)",
        ("collective_s", "train"): "overlap delta all-reduce with local compute",
        ("collective_s", "prefill"): "reshard to cut cross-pod gathers",
        ("collective_s", "decode"): "seq-shard cache so merges stay scalar-sized",
    }
    kind = "train" if "train" in shape else ("prefill" if "prefill" in shape else "decode")
    return hints.get((dom, kind), "")


def to_markdown(recs) -> str:
    lines = [
        "| arch | shape | mesh | status | compute (s) | memory (s) | collective (s) | dominant | useful FLOP ratio | per-dev GB | next lever |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in recs:
        if rec.get("status") == "ok":
            r = rec["roofline"]
            mem_gb = rec["memory"]["total_bytes"] / 1e9
            lines.append(
                f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | ok "
                f"| {fmt_s(r['compute_s'])} | {fmt_s(r['memory_s'])} "
                f"| {fmt_s(r['collective_s'])} | {r['dominant'].replace('_s','')} "
                f"| {r['useful_flop_ratio']:.2f} | {mem_gb:.1f} | {one_liner(rec)} |")
        else:
            reason = rec.get("reason", rec.get("error", ""))[:60]
            lines.append(
                f"| {rec['arch']} | {rec['shape']} | {rec.get('mesh','-')} "
                f"| {rec['status']} | - | - | - | - | - | - | {reason} |")
    return "\n".join(lines)


def to_csv(recs) -> str:
    rows = ["arch,shape,mesh,status,compute_s,memory_s,collective_s,dominant,"
            "useful_flop_ratio,flops_per_chip,hbm_bytes,link_bytes,per_dev_bytes"]
    for rec in recs:
        if rec.get("status") == "ok":
            r = rec["roofline"]
            h = rec["hlo_cost"]
            rows.append(
                f"{rec['arch']},{rec['shape']},{rec['mesh']},ok,"
                f"{r['compute_s']:.6e},{r['memory_s']:.6e},{r['collective_s']:.6e},"
                f"{r['dominant']},{r['useful_flop_ratio']:.4f},{h['flops_per_chip']:.4e},"
                f"{h['hbm_bytes_per_chip']:.4e},{h['link_bytes']:.4e},"
                f"{rec['memory']['total_bytes']}")
        else:
            rows.append(f"{rec['arch']},{rec['shape']},{rec.get('mesh','-')},"
                        f"{rec['status']},,,,,,,,,")
    return "\n".join(rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--format", default="md", choices=["md", "csv"])
    args = ap.parse_args()
    recs = load_records(args.dir)
    print(to_markdown(recs) if args.format == "md" else to_csv(recs))


if __name__ == "__main__":
    main()
