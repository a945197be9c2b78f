"""Evaluate the parked A/B decision rules against tools/ab_results.json.

The rules live in docs/performance.md ("Pending at round-4 close" +
"Round-5 additions"); this tool turns the latest measured runs into
explicit verdicts so flipping defaults is mechanical and auditable:

  * xent_chunk — lm_chunk16384_bs128 vs base; win => default 16384.
  * ring       — ring_ab fwd/bwd Pallas speedups at both local shards;
                 both >1 => default HVDT_RING_PALLAS=1.
  * resnet_1x1 — pallas_vs_conv on the probe shapes; >1.05 anywhere =>
                 wire the fused kernel; else close the lever.

WIN_MARGIN = 1.02: a default only flips on a >=2% end-to-end win —
within-window variance on this chip was measured ~±0.5%
(docs/performance.md), so 2% is comfortably outside noise.
Reads ALL runs, keeps each leg's LATEST successful result.  Prints one
JSON line; exits 0 even when evidence is incomplete (verdict
"unmeasured" — the honest state, never a guess).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIN_MARGIN = 1.02

# Must cover tools/resnet_probe.py SHAPES exactly (kept in sync by
# tests/test_ab_decide.py; not imported — resnet_probe imports jax at
# module scope and this tool must stay dependency-free).
PROBE_SHAPES = {"s3_contract", "s3_expand", "s4_contract", "s4_expand"}


def latest_results(path):
    with open(path) as f:
        hist = json.load(f)
    latest = {}
    for run in hist:
        for r in run.get("results", []):
            if r.get("ok") and r.get("result") is not None:
                latest[r["name"]] = {"at": run.get("at"),
                                     "result": r["result"]}
    return latest


def toks(latest, name):
    entry = latest.get(name)
    if not entry:
        return None
    res = entry["result"]
    return res.get("tokens_per_sec") if isinstance(res, dict) else None


def decide(latest):
    out = {}

    base = toks(latest, "lm_base_bs128_remat")
    chunk = toks(latest, "lm_chunk16384_bs128")
    if chunk and base:
        out["xent_chunk"] = {
            "chunk16384_tok_s": chunk, "baseline_tok_s": base,
            "speedup": round(chunk / base, 4),
            "verdict": ("DEFAULT_16384" if chunk >= base * WIN_MARGIN
                        else "KEEP_8192")}
    else:
        out["xent_chunk"] = {"verdict": "unmeasured"}

    ring = {}
    for shard in (2048, 8192):
        entry = latest.get(f"ring_ab_local{shard}")
        if entry and isinstance(entry["result"], dict):
            r = entry["result"]
            ring[shard] = {"fwd": r.get("fwd_pallas_speedup"),
                           "bwd": r.get("bwd_pallas_speedup"),
                           "bwd_ok": r.get("bwd_correctness_ok"),
                           "platform": r.get("platform")}
    if (set(ring) == {2048, 8192}
            and all(v["platform"] == "tpu" for v in ring.values())):
        # Complete evidence only (one shard measured mid-outage is not a
        # loss — it's unmeasured); same WIN_MARGIN as every other
        # default flip — a 1.00x-1.02x "win" is within the documented
        # within-window variance.
        wins = [s for s, v in ring.items()
                if v["fwd"] and v["bwd"] and v["bwd_ok"]
                and v["fwd"] >= WIN_MARGIN and v["bwd"] >= WIN_MARGIN]
        out["ring"] = {"per_shard": ring,
                       "verdict": ("DEFAULT_RING_PALLAS"
                                   if len(wins) == 2 else "KEEP_JNP")}
    else:
        out["ring"] = {"verdict": "unmeasured",
                       **({"per_shard": ring} if ring else {})}

    out["resnet_1x1"] = _probe_verdict(latest.get("resnet_1x1_probe"))
    out["resnet_1x1_train"] = _probe_verdict(
        latest.get("resnet_1x1_train_probe"))

    def bench_img_s(name):
        entry = latest.get(name)
        if not entry or not isinstance(entry["result"], dict):
            return None, None
        r = entry["result"]
        # stale fallback headlines and CPU probes are not window
        # evidence
        if r.get("platform") != "tpu" or r.get("stale"):
            return None, None
        return r.get("value"), entry.get("at")

    base_img, base_at = bench_img_s("resnet_bench_default")
    fused_img, fused_at = bench_img_s("resnet_bench_fused")
    # Same-run only: the legs are scheduled adjacent precisely so the
    # comparison is within one measurement window — pairing a default
    # from run N with a fused from run N+1 is the cross-window
    # comparison the harness docstring forbids.
    if base_img and fused_img and base_at == fused_at is not None:
        out["resnet_e2e_fused"] = {
            "default_img_s": base_img, "fused_img_s": fused_img,
            "speedup": round(fused_img / base_img, 4),
            "verdict": ("DEFAULT_FUSED" if fused_img >= base_img
                        * WIN_MARGIN else "KEEP_XLA_CONV"),
            "action": ("default HVDT_FUSED_CONV1X1=1 (common/config.py)"
                       if fused_img >= base_img * WIN_MARGIN else
                       "keep off; record the e2e number")}
    else:
        out["resnet_e2e_fused"] = {"verdict": "unmeasured"}

    return out


def _probe_verdict(entry):
    """Shared rule for the affine and train-form 1x1 probes."""
    if not (entry and isinstance(entry["result"], list)):
        return {"verdict": "unmeasured"}
    rows = {r["shape"]: {"pallas_vs_conv": r.get("pallas_vs_conv"),
                         "matmul_vs_conv": r.get("matmul_vs_conv"),
                         "ok": r.get("correctness_ok"),
                         "platform": r.get("platform")}
            for r in entry["result"]}
    # platform gate: interpret-mode CPU rows are complete and
    # correctness-pass but time nothing real — only chip rows may
    # feed a permanent verdict (the bench.py last-good discipline).
    measured = {s for s, v in rows.items()
                if v["ok"] and v["pallas_vs_conv"]
                and v["platform"] == "tpu"}
    if measured != PROBE_SHAPES:
        # CLOSE_LEVER is permanent — it may only come from a FULL
        # probe (every shape correctness-passed AND Pallas-timed);
        # a crashed or miscomparing run stays "unmeasured".
        return {"verdict": "unmeasured", "per_shape": rows,
                "missing": sorted(PROBE_SHAPES - measured)}
    wins = sorted(s for s in measured
                  if rows[s]["pallas_vs_conv"] > 1.05)
    return {"per_shape": rows,
            "verdict": "WIRE_FUSED_KERNEL" if wins else "CLOSE_LEVER",
            "winning_shapes": wins}


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        REPO, "tools", "ab_results.json")
    latest = latest_results(path)
    print(json.dumps({"decisions": decide(latest),
                      "legs_seen": sorted(latest)}, indent=1))


if __name__ == "__main__":
    main()
