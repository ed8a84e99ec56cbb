"""Aggregates the dry-run JSONs into the §Roofline table.

Not a paper table — the assignment's roofline deliverable. Reads
experiments/dryrun/*.json produced by repro.launch.dryrun.
"""
import glob
import json
import os

from repro.launch.mesh import chip_peaks


def load_records(out_dir="experiments/dryrun"):
    recs = []
    for f in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        recs.append(json.load(open(f)))
    return recs


def format_table(recs, mesh="pod1"):
    lines = []
    hdr = (f"{'arch':22s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'coll_s':>8s} {'bound':>10s} {'useful':>7s} {'temp_GB':>8s}")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in recs:
        if r.get("mesh") != mesh:
            continue
        if r["status"] == "skipped":
            lines.append(f"{r['arch']:22s} {r['shape']:12s} "
                         f"{'skipped: ' + r['reason'][:46]}")
            continue
        if r["status"] != "ok":
            lines.append(f"{r['arch']:22s} {r['shape']:12s} ERROR")
            continue
        t = r["roofline"]
        temp = r["memory_analysis"].get("temp_size_in_bytes", 0) / 1e9
        lines.append(
            f"{r['arch']:22s} {r['shape']:12s} {t['compute_s']:10.3f} "
            f"{t['memory_s']:10.3f} {t['collective_s']:8.3f} "
            f"{t['dominant'].replace('_s',''):>10s} "
            f"{r.get('useful_flops_ratio', 0):7.2f} {temp:8.1f}")
    return "\n".join(lines)


def run():
    recs = load_records()
    ok_count = sum(1 for r in recs if r["status"] == "ok")
    skip_count = sum(1 for r in recs if r["status"] == "skipped")
    err_count = sum(1 for r in recs if r["status"] not in ("ok", "skipped"))
    rows = [
        ("roofline/records", 0.0,
         f"{ok_count} ok, {skip_count} skipped, {err_count} error "
         f"(of {len(recs)})"),
    ]
    if recs:
        # dominant-term census over ok records (pod1)
        from collections import Counter
        c = Counter(r["roofline"]["dominant"] for r in recs
                    if r["status"] == "ok" and r["mesh"] == "pod1")
        rows.append(("roofline/dominant_census_pod1", 0.0, dict(c)))
        over = [f"{r['arch']}/{r['shape']}" for r in recs
                if r["status"] == "ok" and r["mesh"] == "pod1"
                and r["memory_analysis"].get("temp_size_in_bytes", 0)
                + r["memory_analysis"].get("argument_size_in_bytes", 0)
                > chip_peaks(r["device_kind"]).hbm_bytes]
        rows.append(("roofline/over_hbm_pod1", 0.0,
                     over if over else "all fit 16GiB"))
    ok = err_count == 0 and ok_count > 0
    return rows, ok
