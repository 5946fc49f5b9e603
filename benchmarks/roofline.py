"""Roofline table: reads the dry-run artifacts (results/dryrun_*.json) and
prints the per-(arch x shape) three-term analysis — deliverable (g).

``--solve BENCH.json`` switches to the DD-KF solve roofline: from a
streaming_bench report it rebuilds each arm's decomposition shapes,
prices one Schwarz iteration per device as three terms — compute
(~6mw + 2w^2 flops), memory (the Schwarz sweep's two HBM passes over
the (m, w) operator block) and collective (the
m-vector all-reduce bytes from ``ddkf.comm_model`` under torus-aware
mesh pricing) — and prints the modelled bound next to the measured
solve-phase p50 from the report's journalled phase spans.

  PYTHONPATH=src python benchmarks/roofline.py            # dry-run table
  PYTHONPATH=src python benchmarks/roofline.py \
      --solve streaming-shardmap.json                     # solve roofline
"""
from __future__ import annotations

import argparse
import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def load(multi_pod=False):
    name = "dryrun_multipod.json" if multi_pod else "dryrun_singlepod.json"
    path = os.path.join(RESULTS, name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def print_table(rows=None, multi_pod=False):
    data = rows or load(multi_pod)
    if not data:
        print("(no dry-run results yet — run repro.launch.dryrun)")
        return []
    hdr = (f"{'arch':22s} {'shape':12s} {'comp_ms':>9s} {'mem_ms':>9s} "
           f"{'coll_ms':>9s} {'bound':>10s} {'useful':>7s} {'roofl':>6s} "
           f"{'peakGiB':>8s}")
    print(hdr)
    print("-" * len(hdr))
    out = []
    for key in sorted(data):
        r = data[key]
        if r.get("status") == "skipped":
            arch, shape = key.split("|")
            print(f"{arch:22s} {shape:12s} {'—':>9s} {'—':>9s} {'—':>9s} "
                  f"{'skipped':>10s}")
            continue
        if r.get("status") != "ok":
            continue
        peak = r.get("memory", {}).get("peak_per_device", 0) / 2**30
        print(f"{r['arch']:22s} {r['shape']:12s} "
              f"{r['compute_s']*1e3:9.2f} {r['memory_s']*1e3:9.2f} "
              f"{r['collective_s']*1e3:9.2f} {r['dominant']:>10s} "
              f"{r['useful_flops_frac']:7.3f} {r['roofline_frac']:6.3f} "
              f"{peak:8.2f}")
        out.append(r)
    return out


LEVERS = {
    # dominant term -> the established lever family (EXPERIMENTS.md §Perf)
    "compute": "already compute-bound: raise MXU utilization via larger "
               "per-device batch or fewer remat recomputes",
    "memory": "attention-score traffic / remat reads: Pallas flash kernel "
              "on TPU, larger fusion scope, bf16 intermediates",
    "collective": "sharding-level: EP for MoE grads (PERF-A2/C1), dp "
                  "profile for small-d archs (PERF-B0), replicated embed "
                  "(PERF-B3), microbatching",
}


def what_would_move(row) -> str:
    d = row["dominant"]
    base = LEVERS.get(d, "")
    if row["shape"].startswith(("decode", "long")):
        return ("serving regime: batch more requests per step; " + base)
    return base


def summarize():
    data = load()
    if not data:
        return
    ok = [r for r in data.values() if r.get("status") == "ok"]
    from collections import Counter
    doms = Counter(r["dominant"] for r in ok)
    worst = sorted(ok, key=lambda r: r["roofline_frac"])[:3]
    coll = sorted(ok, key=lambda r: -r["collective_s"])[:3]
    print(f"\n{len(ok)} cells analyzed; bottleneck mix: {dict(doms)}")
    print("worst roofline fraction:",
          [(r["arch"], r["shape"], round(r["roofline_frac"], 3))
           for r in worst])
    print("most collective-bound:",
          [(r["arch"], r["shape"], round(r["collective_s"] * 1e3, 1))
           for r in coll])
    print("\nlever per dominant term (details: EXPERIMENTS.md §Perf):")
    for d in doms:
        print(f"  {d}: {LEVERS[d]}")


# -- DD-KF solve roofline (--solve) ---------------------------------------

# Conservative single-device peaks; override per machine.  The defaults
# describe a TPU-v4-class chip (f32 MXU, HBM2e, one ICI link) — on a CPU
# runner the measured column will sit far above the bound, which is the
# point: the table shows how far the *observed* solve phase is from the
# shapes' hardware-limit story, whichever term dominates.
PEAK_FLOPS = 9.2e13       # flop/s
PEAK_MEMBW = 1.2e12       # HBM bytes/s
PEAK_COLLBW = 9.0e10      # collective bytes/s per device


def _rebuild_domain(meta: dict):
    """Domain object back from a journal's ``Domain.describe()`` dict."""
    from repro.core import domain as domain_mod
    from repro.core import kdtree as kdtree_mod
    kind = meta.get("kind", "interval1d")
    if kind == "interval1d":
        return domain_mod.Interval1D(n=meta["n"], p=meta["p"])
    if kind == "shelf2d":
        return domain_mod.ShelfTiling2D(nx=meta["nx"], ny=meta["ny"],
                                        pr=meta["pr"], pc=meta["pc"])
    if kind == "kdtree":
        return kdtree_mod.KDTreeDomain(nx=meta["nx"], ny=meta["ny"],
                                       p=meta["p"])
    raise ValueError(f"unknown domain kind {kind!r}")


def solve_bound(meta: dict, config: dict, kernel: str,
                peak_flops=PEAK_FLOPS, peak_membw=PEAK_MEMBW,
                peak_collbw=PEAK_COLLBW) -> dict:
    """Three-term per-solve bound (seconds) for one arm's shapes.

    Rebuilds the arm's *initial* decomposition (DyDD may move boundaries
    later; w only shrinks under balancing, so this is the conservative
    shape).  Per device and iteration: ~6mw + 2w^2 flops (two stacked
    matmats + the transpose product + the local inverse matvec), operator
    bytes = 2 * m * w * itemsize (the sweep's two passes), and
    the collective term is comm_model's per-device pricing of the
    configured exchange under the domain's torus mesh shape.
    """
    from repro.core import ddkf
    dom = _rebuild_domain(meta)
    overlap = int(config.get("overlap", 0))
    iters = int(config.get("iters", 100))
    m_obs = int(config.get("m", 0))
    itemsize = 8  # streaming_bench runs under jax_enable_x64
    dec = dom.decomposition(overlap=overlap)
    w = dec.pad_width
    m = dom.n + m_obs          # stacked rows: state block + observations
    comm = config.get("comm", "allreduce")
    halo = dec.halo_exchange if comm == "neighbour" else None
    stats = ddkf.comm_model(dom.n, m, dom.p, itemsize, halo=halo,
                            comm=comm, mesh_shape=dom.mesh_axes()[1])
    flops = 6.0 * m * w + 2.0 * w * w
    mem_bytes = 2 * m * w * itemsize
    coll_bytes = stats["bytes_per_iter_total"] / dom.p \
        + stats["mvec_bytes_per_device"]
    terms = {
        "compute_s": iters * flops / peak_flops,
        "memory_s": iters * mem_bytes / peak_membw,
        "collective_s": iters * coll_bytes / peak_collbw,
    }
    dominant = max(terms, key=terms.get)
    return {
        "p": dom.p, "m": m, "w": w, "iters": iters, "kernel": kernel,
        **terms,
        "bound_s": terms[dominant],
        "dominant": dominant.removesuffix("_s"),
    }


def print_solve_table(report: dict, peak_flops=PEAK_FLOPS,
                      peak_membw=PEAK_MEMBW, peak_collbw=PEAK_COLLBW):
    config = report.get("config", {})
    hdr = (f"{'scenario/arm':32s} {'p':>3s} {'m':>6s} {'w':>5s} "
           f"{'kern':>6s} {'comp_ms':>8s} {'mem_ms':>8s} {'coll_ms':>8s} "
           f"{'bound_ms':>9s} {'meas_ms':>9s} {'x_bound':>8s} "
           f"{'dominant':>10s}")
    print(hdr)
    print("-" * len(hdr))
    rows = []
    for name, sc in sorted(report.get("scenarios", {}).items()):
        for arm in ("static", "dydd"):
            if arm not in sc:
                continue
            rec = sc[arm]
            kernel = rec.get("solver_kernel",
                             config.get("solver_kernel", "jnp"))
            b = solve_bound(rec.get("domain", {}), config, kernel,
                            peak_flops, peak_membw, peak_collbw)
            # Measured solve phase p50 from the journalled phase spans.
            meas = rec.get("summary", {}).get("phases", {}) \
                      .get("solve", {}).get("p50")
            ratio = (meas / b["bound_s"]) if meas and b["bound_s"] > 0 \
                else None
            print(f"{name + '/' + arm:32s} {b['p']:3d} {b['m']:6d} "
                  f"{b['w']:5d} {kernel[:6]:>6s} "
                  f"{b['compute_s']*1e3:8.3f} {b['memory_s']*1e3:8.3f} "
                  f"{b['collective_s']*1e3:8.3f} {b['bound_s']*1e3:9.3f} "
                  f"{(meas or 0)*1e3:9.2f} "
                  f"{ratio if ratio is not None else float('nan'):8.1f} "
                  f"{b['dominant']:>10s}")
            rows.append({"scenario": name, "arm": arm, "measured_s": meas,
                         **b})
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--solve", default=None, metavar="BENCH.json",
                    help="streaming_bench report: print the DD-KF solve "
                    "roofline instead of the dry-run table")
    ap.add_argument("--peak-flops", type=float, default=PEAK_FLOPS)
    ap.add_argument("--peak-membw", type=float, default=PEAK_MEMBW)
    ap.add_argument("--peak-collbw", type=float, default=PEAK_COLLBW)
    ap.add_argument("--multi-pod", action="store_true",
                    help="dry-run table: read dryrun_multipod.json")
    cli = ap.parse_args()
    if cli.solve:
        with open(cli.solve) as f:
            print_solve_table(json.load(f), cli.peak_flops,
                              cli.peak_membw, cli.peak_collbw)
    else:
        print_table(multi_pod=cli.multi_pod)
        summarize()
