"""Quiet-window TPU A/B runner (VERDICT r3 #1-#4 evidence collector).

Runs a fixed sequence of experiment legs as subprocesses on the real
chip, parses each leg's metric line, and appends everything to
``tools/ab_results.json``: leg 0 is the stock ResNet bench, then the LM
legs, then the flash-backward kernel A/Bs.

Sequential by construction — one process holds the chip at a time, and
only within-one-window comparisons are valid (docs/performance.md).
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable

LM = [PY, os.path.join(REPO, "examples", "jax_transformer_lm.py"),
      "--preset", "bert-large", "--dp", "1", "--tp", "1",
      "--dtype", "bfloat16"]
TOKS = re.compile(r"(\d+) tokens/sec, ~([\d.]+) model TFLOP/s")


def lm_leg(name, extra, steps="30", timeout=900, env=None):
    return {"name": name,
            "cmd": LM + ["--steps", steps] + extra,
            "timeout": timeout, "env": env,
            "parse": lambda out: (
                {"tokens_per_sec": int(TOKS.search(out).group(1)),
                 "model_tflops": float(TOKS.search(out).group(2))}
                if TOKS.search(out) else None)}


def json_leg(name, cmd, timeout=900, env=None):
    def parse(out):
        for line in reversed(out.strip().splitlines()):
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except ValueError:
                    continue
        return None
    return {"name": name, "cmd": cmd, "timeout": timeout, "parse": parse,
            "env": env}


def jsonl_leg(name, cmd, timeout=900, expect=None):
    """All JSON lines, in order (multi-shape probes emit one per shape).

    ``expect``: required row count — a probe that crashes mid-run after
    emitting a prefix of its shapes must record as FAILED, not as a
    complete measurement (``require_rc0`` backs this with the exit
    code)."""
    def parse(out):
        rows = []
        for line in out.strip().splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    continue
        if not rows or (expect is not None and len(rows) != expect):
            return None
        return rows
    return {"name": name, "cmd": cmd, "timeout": timeout, "parse": parse,
            "require_rc0": True}


def raw_leg(name, cmd, timeout=900, keep=8000, marker="by category:",
            env=None):
    """Keep stdout from the report marker on (profile tables etc.).
    Success requires the marker — partial stdout before a crash must not
    record as ok."""
    def parse(out):
        i = out.find(marker)
        if i < 0:
            return None
        return {"raw": out[i:i + keep]}
    return {"name": name, "cmd": cmd, "timeout": timeout, "parse": parse,
            "env": env}


LEGS = [
    # The headline bench first.
    json_leg("resnet_bench_default",
             [PY, os.path.join(REPO, "bench.py")], timeout=1500),
    # IMMEDIATELY after the default: the FULL bench with every eligible
    # bottleneck 1x1 routed through the fused Pallas kernels
    # (models/resnet.py _conv_bn) — adjacent legs give the tightest
    # within-window e2e A/B; >=2% img/s flips HVDT_FUSED_CONV1X1.
    json_leg("resnet_bench_fused",
             [PY, os.path.join(REPO, "bench.py")], timeout=1500,
             env={"HVDT_FUSED_CONV1X1": "1",
                  "HVDT_BENCH_PROFILE": "0"}),
    # LM: reproduce the round-2/3 baseline.  (The no-remat legs are
    # ANSWERED — r4 measured OOM at batch>=32, tools/ab_results.json —
    # and removed; remat "full" is the only feasible bs128 config.)
    lm_leg("lm_base_bs128_remat", ["--batch", "128"]),
    # Where do the non-matmul 45% of the bs128 step go?  3-step XPlane
    # per-category breakdown (examples/jax_transformer_lm.py --profile).
    raw_leg("lm_profile_bs128",
            LM + ["--batch", "128", "--steps", "10", "--profile"],
            timeout=1200),
    # bs64 with the (now-default) chunked xent at a long timed region —
    # the round-2 49.5 TFLOP bs64 row predates both.
    lm_leg("lm_bs64_long", ["--batch", "64", "--steps", "120"],
           timeout=1200),
    # Full-Pallas attention at the flagship shape: round-2 measured XLA
    # attention ~1.5x faster than the kernel forward with the blockwise-XLA
    # backward at seq 512.  Since PR 27 the backward is a Pallas call too
    # (PERF.md section 6), so this leg is kernel + kernel: if it beats XLA
    # end to end here, the auto gate's 4 GB threshold is wrong (S1).
    lm_leg("lm_flash_bs128", ["--batch", "128"],
           env={"HVDT_FLASH_ATTENTION": "on"}),
    # Chunked-xent scan granularity: 2 chunks of 16384 vs 4 of 8192 —
    # fewer sequential scan steps vs a 4.3 GB live logits tile.
    lm_leg("lm_chunk16384_bs128", ["--batch", "128",
                                   "--loss-chunk", "16384"]),
    # Ring attention per-step block primitives, Pallas vs jnp (the
    # HVDT_RING_PALLAS evidence — sp>=2 can't run on one chip, but the
    # ring cost is sp repetitions of exactly these two per-device ops).
    json_leg("ring_ab_local2048",
             [PY, os.path.join(REPO, "tools", "ring_ab.py"),
              "--local-seqs", "2048", "--batch", "2"], timeout=1200),
    json_leg("ring_ab_local8192",
             [PY, os.path.join(REPO, "tools", "ring_ab.py"),
              "--local-seqs", "8192", "--batch", "1"], timeout=1200),
    # Below-XLA ResNet roofline probe (VERDICT r4 weak #3): fused
    # 1x1-conv+BN Pallas epilogue vs XLA conv/matmul scheduling on the
    # four hot bottleneck shapes — one JSON row per shape.
    jsonl_leg("resnet_1x1_probe",
              [PY, os.path.join(REPO, "tools", "resnet_probe.py")],
              timeout=1500, expect=4),
    # TRAIN-form BN (batch stats): the fused kernel emits z + stat
    # partials in one pass, saving one full read of z vs XLA's
    # stats-then-normalize schedule.
    jsonl_leg("resnet_1x1_train_probe",
              [PY, os.path.join(REPO, "tools", "resnet_probe.py"),
               "--form", "train"],
              timeout=1500, expect=4),
    # ResNet dispatch-gap probe: N steps per jit call via lax.fori_loop
    # (larger batches were already measured WORSE in round 2 — activation
    # traffic scales with batch; docs/performance.md).
    json_leg("resnet_steps_per_call10",
             [PY, os.path.join(REPO, "bench.py"), "--steps-per-call", "10",
              "--num-batches-per-iter", "5"], timeout=1500),
]

# Failure tails that mean THE LEG is infeasible (OOM etc.), not that the
# chip is down — these must not trip the consecutive-failure abort (r4:
# two no-remat OOM legs aborted the harness while the chip was healthy).
_LEG_SPECIFIC = ("RESOURCE_EXHAUSTED", "AllocateBuffer", "Allocation type",
                 "out of memory", "OOM")


def run_leg(leg, env):
    t0 = time.time()
    if leg.get("env"):
        env = dict(env, **leg["env"])
    try:
        proc = subprocess.run(leg["cmd"], env=env, capture_output=True,
                              text=True, timeout=leg["timeout"], cwd=REPO)
        out = proc.stdout + "\n" + proc.stderr
        parsed = leg["parse"](proc.stdout)
        if parsed is not None and leg.get("require_rc0") \
                and proc.returncode != 0:
            # Parsable prefix + crash = incomplete evidence, not a run.
            parsed = None
        return {"name": leg["name"], "ok": parsed is not None,
                "wall_s": round(time.time() - t0, 1),
                "result": parsed,
                "tail": None if parsed else out[-800:]}
    except subprocess.TimeoutExpired:
        return {"name": leg["name"], "ok": False,
                "wall_s": round(time.time() - t0, 1),
                "result": None, "tail": f"timeout {leg['timeout']}s"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="comma-separated leg names")
    ap.add_argument("--out", default=os.path.join(REPO, "tools",
                                                  "ab_results.json"))
    args = ap.parse_args()
    legs = LEGS
    if args.only:
        want = set(args.only.split(","))
        legs = [l for l in LEGS if l["name"] in want]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    results = []
    fails = 0
    for leg in legs:
        print(f"=== {leg['name']} ===", flush=True)
        r = run_leg(leg, env)
        print(json.dumps(r), flush=True)
        results.append(r)
        leg_specific = r["tail"] and any(m in r["tail"]
                                         for m in _LEG_SPECIFIC)
        # OOM legs neither accumulate toward chip-down nor clear evidence
        # of it — only a SUCCESS proves the chip is alive.
        fails = 0 if r["ok"] else (fails if leg_specific else fails + 1)
        if fails >= 2:
            print("two consecutive failures — chip likely down, aborting",
                  flush=True)
            break
    hist = []
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                hist = json.load(f)
        except ValueError:
            hist = []
    hist.append({"at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "results": results})
    with open(args.out, "w") as f:
        json.dump(hist, f, indent=1)
    print(f"saved {len(results)} legs -> {args.out}")


if __name__ == "__main__":
    main()
