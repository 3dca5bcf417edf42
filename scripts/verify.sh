#!/usr/bin/env bash
# CI entry point: the FULL tier-1 suite as the gate, the EXPERIMENTS.md
# freshness audit, a 3-config mini-sweep through the full trace → partition →
# place (batched quad + greedy construction) → batched-simulate → report
# pipeline, the observability arm (trace/metrics schema validation,
# recording-on ≡ recording-off byte-identity, <5% overhead gate), the
# resilience and backpressure mini-grids (degraded and credit nocsim arms
# end to end), a gated nocsim coverage floor, and the resumable dry-run
# artifact sweep.
#
# The whole suite gates; there is no "pre-existing failures" carve-out.
# Tests run on the CPU; the chip is reached by running `python chip_smoke.py`
# on a TPU host.  Property tests never skip:
# tests/_hypothesis_compat.py vendors a minimal fallback runner when the
# offline container has no hypothesis wheel.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== test extras (hypothesis for the property tests) =="
if python -c "import hypothesis" 2>/dev/null; then
    echo "hypothesis already installed"
elif pip install -q "hypothesis>=6" 2>/dev/null || pip install -q -e ".[test]" 2>/dev/null; then
    echo "installed hypothesis via the [test] extra"
else
    echo "hypothesis unavailable (offline container without a wheel);"
    echo "property tests run on the vendored fallback (tests/_hypothesis_compat.py)"
fi

echo "== gating tests (full tier-1 suite, on the CPU) =="
JAX_PLATFORMS=cpu python -m pytest -x -q

echo "== scale memory budget (sparse pipeline @ soc-pokec scale 0.1) =="
# The published-size pipeline guard: one scale-0.1 soc-pokec sweep (3.06M
# edges) under a peak-RSS assertion (tests/test_scale_memory.py, 2 GiB
# budget vs ~1 GiB measured).  Marked `slow` + env-gated so tier-1 above
# stays fast; VERIFY_SKIP_SCALE_RSS=1 skips it on constrained containers.
if [[ "${VERIFY_SKIP_SCALE_RSS:-0}" == "1" ]]; then
    echo "skipped (VERIFY_SKIP_SCALE_RSS=1)"
else
    REPRO_SCALE_RSS=1 python -m pytest -q tests/test_scale_memory.py
fi

echo "== EXPERIMENTS.md freshness vs committed payloads =="
python -m repro.experiments.report --check

echo "== parity/determinism contract lint =="
# Pure-local AST pass: fails on any finding not grandfathered in
# artifacts/lint_baseline.json (and on stale baseline entries — the
# baseline is shrink-only), then asserts the ARCHITECTURE.md parity table
# still matches the @parity_pair registry.
python -m repro.analysis.lint src --check-baseline
python -m repro.analysis.parity_table --check

echo "== mini sweep (3 configs) =="
out="$(mktemp -d)"
python -m repro.experiments.run --grid mini \
    --md "$out/EXPERIMENTS.mini.md" --json "$out/BENCH_sweep.mini.json" \
    --cache-dir "$out/cache" --sweeps-dir "$out/sweeps"
test -s "$out/EXPERIMENTS.mini.md"
test -s "$out/BENCH_sweep.mini.json"
python - "$out/BENCH_sweep.mini.json" <<'EOF'
import json, sys
payload = json.load(open(sys.argv[1]))
assert payload["records"], "mini sweep produced no records"
assert payload["comparisons"], "mini sweep produced no comparisons"
for c in payload["comparisons"]:
    assert c["speedup"] > 1.0 and c["hop_decrease"] > 1.0, c
ps = payload["placement_stats"]
assert ps["batched_configs"] >= 2, f"batched placement path not exercised: {ps}"
assert ps["greedy_constructed"] >= 1, f"batched greedy construction not exercised: {ps}"
assert ps["h_worse_than_serial_configs"] == 0, f"batched H worse than serial: {ps}"
assert any(
    "2opt[batch]" in r["placement_method"] for r in payload["records"]
), "no record carries the batched-engine method tag"
assert any(
    r["placement_method"] == "greedy+2opt[batch]" for r in payload["records"]
), "no record went through the stacked greedy construction"
c = payload["comparisons"][0]
print(f"mini sweep ok: speedup={c['speedup']:.2f}x hop_decrease={c['hop_decrease']:.2f}x "
      f"placement batched={ps['batched_configs']} greedy-constructed="
      f"{ps['greedy_constructed']} (H ratio max {ps['h_vs_serial_max_ratio']:.4f})")
EOF
rm -rf "$out"

echo "== observability arm (trace/metrics on the mini grid) =="
# Flight-recorder contract: --trace-out/--metrics-out produce schema-valid
# Chrome-trace + metrics JSON, recording on vs off leaves the rendered
# artifacts byte-identical (deterministic clock), and the all-in wall-clock
# overhead of tracing stays under 5%.
oout="$(mktemp -d)"
python -m repro.experiments.run --grid mini -q --cache-dir "$oout/cache" \
    --md "$oout/warm.md" --json "$oout/warm.json"   # warm the sweep cache
REPRO_OBS_DETERMINISTIC=1 python -m repro.experiments.run --grid mini -q \
    --cache-dir "$oout/cache" --md "$oout/off.md" --json "$oout/off.json"
REPRO_OBS_DETERMINISTIC=1 python -m repro.experiments.run --grid mini -q \
    --cache-dir "$oout/cache" --md "$oout/on.md" --json "$oout/on.json" \
    --trace-out "$oout/trace.json" --metrics-out "$oout/metrics.json"
cmp "$oout/off.md" "$oout/on.md"
cmp "$oout/off.json" "$oout/on.json"
echo "recording on vs off: rendered artifacts byte-identical"
python -m repro.obs.validate "$oout/trace.json" --schema schemas/trace.schema.json
python -m repro.obs.validate "$oout/metrics.json" --schema schemas/metrics.schema.json
python - "$oout/trace.json" "$oout/metrics.json" <<'EOF'
import json, os, sys
trace = json.load(open(sys.argv[1]))
spans = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
assert "pipeline.sweep" in spans and "sweep.placement" in spans, sorted(spans)
counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
assert counters, "no per-link counter tracks in the trace"
assert trace["otherData"]["dropped_spans"] == 0, trace["otherData"]
heat = json.load(open(os.path.splitext(sys.argv[1])[0] + ".heatmap.json"))
assert heat["tracks"], "heatmap artifact has no tracks"
snap = json.load(open(sys.argv[2]))
stages = snap["non_comparable"]["sweep.stage_seconds"]["series"]
assert any(s["labels"]["stage"] == "placement" for s in stages), stages
tracks = {(e["pid"], e["name"]) for e in counters}
print(f"obs arm ok: {len(spans)} span names, {len(tracks)} counter tracks,"
      f" {len(heat['tracks'])} heatmap tracks")
EOF
# Overhead gate: tracing + flight recording must cost <5% of an untraced
# end-to-end mini run.  The two sides are measured separately because they
# need different precision: the NUMERATOR (traced-minus-untraced CPU) is a
# ~15-20ms signal that end-to-end subprocess timings cannot resolve — cold
# interpreter + import CPU jitters by ±50ms run to run — so it is measured
# in-process on a warm cache as the median of order-alternated paired reps
# (imports and cache warmup cancel exactly; CPU time via getrusage, immune
# to wall-clock scheduling noise).  The DENOMINATOR (untraced full-run
# cost) only needs ~5% precision, so a median of 3 cold child-CPU runs is
# plenty.
python - "$oout" <<'EOF'
import os, resource, statistics, subprocess, sys
out = sys.argv[1]
argv = ["--grid", "mini", "-q", "--cache-dir", os.path.join(out, "cache"),
        "--md", os.path.join(out, "t.md"), "--json", os.path.join(out, "t.json")]
traced_extra = ["--trace-out", os.path.join(out, "t.trace.json"),
                "--metrics-out", os.path.join(out, "t.metrics.json")]
cold_cmd = [sys.executable, "-m", "repro.experiments.run"] + argv
def cold():
    r0 = resource.getrusage(resource.RUSAGE_CHILDREN)
    subprocess.run(cold_cmd, check=True, capture_output=True)
    r1 = resource.getrusage(resource.RUSAGE_CHILDREN)
    return (r1.ru_utime + r1.ru_stime) - (r0.ru_utime + r0.ru_stime)
cold()  # warm the sweep cache
denom = statistics.median(cold() for _ in range(3))
from repro import obs
from repro.experiments.run import main
def rep(extra):
    r0 = resource.getrusage(resource.RUSAGE_SELF)
    main(argv + extra)
    r1 = resource.getrusage(resource.RUSAGE_SELF)
    obs.disable_tracing()
    obs.get_tracer().reset()
    return (r1.ru_utime + r1.ru_stime) - (r0.ru_utime + r0.ru_stime)
rep([]); rep(traced_extra)  # warm both paths
diffs = []
for i in range(7):
    if i % 2 == 0:
        p = rep([]); t = rep(traced_extra)
    else:
        t = rep(traced_extra); p = rep([])
    diffs.append(t - p)
num = statistics.median(diffs)
overhead = num / denom * 100.0
assert overhead < 5.0, (
    f"tracing overhead {overhead:.1f}% >= 5%"
    f" ({num*1e3:.1f}ms added to a {denom*1e3:.0f}ms untraced run)"
)
print(f"obs overhead ok: +{overhead:.1f}% ({num*1e3:.1f}ms obs cost,"
      f" median of 7 paired reps, vs {denom*1e3:.0f}ms untraced run)")
EOF
rm -rf "$oout"

echo "== resilience arm (mini faults grid + crash-resume smoke) =="
# Degraded-fabric pipeline end to end: the 2-unit minifaults grid through
# FaultSet -> detour routing -> degraded nocsim (jax parity when available)
# -> evacuation/repair, then a literal kill -9 mid-sweep with a journaled
# --resume that must reproduce the uninterrupted artifact byte for byte.
rout="$(mktemp -d)"
python -m repro.experiments.run --grid minifaults --backend auto -q \
    --cache-dir "$rout/cache" --sweeps-dir "$rout/a" --journal "$rout/a.journal.json"
python - "$rout/a/minifaults.json" <<'EOF'
import json, sys
payload = json.load(open(sys.argv[1]))["faults"]
recs = payload["records"]
assert recs, "minifaults produced no unit records"
rates = {r["fault_rate"] for r in recs}
assert rates == {0.0, 0.05}, f"unexpected fault rates {rates}"
clean = next(r for r in recs if r["fault_rate"] == 0.0)
faulted = next(r for r in recs if r["fault_rate"] == 0.05)
assert clean["win"] > 1.0, f"proposed scheme does not win on the clean fabric: {clean['win']}"
assert faulted["num_dead_links"] > 0 and faulted["num_detoured_flows"] > 0, faulted
assert payload["repair"], "no repair-ledger rows"
for row in payload["repair"]:
    assert row["batch_parity"], f"repair serial/batched mismatch: {row}"
    assert row["h_repaired"] <= row["h_evacuated"] + 1e-9, row
assert not payload["quarantined"], f"quarantined units: {payload['quarantined']}"
parity = payload["backend_parity_max_rel"]
if parity is not None:  # jax was available -> the degraded arm ran both backends
    assert parity <= payload["parity_rtol"], f"degraded-arm parity {parity:.3e}"
    print(f"resilience ok: win {clean['win']:.2f}x -> {faulted['win']:.2f}x at 5% faults;"
          f" jax parity {parity:.2e} <= {payload['parity_rtol']:g}")
else:
    print(f"resilience ok: win {clean['win']:.2f}x -> {faulted['win']:.2f}x at 5% faults;"
          " jax absent, numpy-only")
EOF
# Crash-resume smoke: kill -9 between journal flushes, resume, compare bytes.
REPRO_FAULTS_UNIT_DELAY=2.0 python -m repro.experiments.run --grid minifaults \
    --backend auto -q --cache-dir "$rout/cache" --sweeps-dir "$rout/b" \
    --journal "$rout/b.journal.json" &
victim=$!
for _ in $(seq 1 200); do
    python - "$rout/b.journal.json" <<'EOF' && break
import json, sys
try:
    raise SystemExit(0 if json.load(open(sys.argv[1])).get("units") else 1)
except (FileNotFoundError, json.JSONDecodeError):
    raise SystemExit(1)
EOF
    sleep 0.1
done
kill -9 "$victim" 2>/dev/null || true
wait "$victim" 2>/dev/null || true
python -m repro.experiments.run --grid minifaults --backend auto -q --resume \
    --cache-dir "$rout/cache" --sweeps-dir "$rout/b" --journal "$rout/b.journal.json"
cmp "$rout/a/minifaults.json" "$rout/b/minifaults.json"
echo "crash-resume smoke ok: resumed artifact is byte-identical"
rm -rf "$rout"

echo "== backpressure arm (minicredit grid: credit flow control end to end) =="
# Closed-loop credit arm through the sweep pipeline: the 2-config minicredit
# grid runs the open + credit(d=1,4) record sets, the infinite-credit
# convergence audit (numpy bit-exact, jax within parity), and the dual
# backends over the identical stacked programs.
bout="$(mktemp -d)"
# minicredit is a CI-only grid (no EXPERIMENTS.md section), so it stores no
# artifacts/sweeps entry; --json captures its machine-readable payload.
python -m repro.experiments.run --grid minicredit --backend auto -q \
    --cache-dir "$bout/cache" --sweeps-dir "$bout/sweeps" \
    --json "$bout/minicredit.json"
python - "$bout/minicredit.json" <<'EOF'
import json, sys
payload = json.load(open(sys.argv[1]))["contention"]
recs = payload["records"]
assert recs, "minicredit produced no contended records"
depths = {r["buffer_depth"] for r in recs if r["flow_control"] == "credit"}
assert depths == {1.0, 4.0}, f"unexpected credit depth axis {depths}"
n_open = sum(r["flow_control"] == "open" for r in recs)
n_credit = sum(r["flow_control"] == "credit" for r in recs)
assert n_open > 0 and n_credit == 2 * n_open, (n_open, n_credit)
inf_np = payload["credit_inf_numpy_max_abs"]
assert inf_np == 0.0, f"infinite-credit numpy audit not bit-exact: {inf_np}"
rtol = payload["parity_rtol"]
parity = payload["backend_parity_max_rel"]
inf_jax = payload["credit_inf_jax_max_rel"]
if parity is not None:  # jax available -> both backends ran every arm
    assert parity <= rtol, f"credit-arm parity {parity:.3e} > {rtol:g}"
    assert inf_jax is not None and inf_jax <= rtol, f"inf-credit jax {inf_jax}"
    print(f"backpressure ok: {n_credit} credit records over depths {sorted(depths)};"
          f" inf-credit numpy exact, jax {inf_jax:.2e}; parity {parity:.2e}")
else:
    print(f"backpressure ok: {n_credit} credit records over depths {sorted(depths)};"
          " inf-credit numpy exact; jax absent, numpy-only")
EOF
rm -rf "$bout"

echo "== nocsim line coverage (property/differential suites vs the steppers) =="
# The conservation-law harness claims to exercise every stepper arm; hold it
# to that with a line-coverage floor over repro.nocsim when pytest-cov is
# importable.  The offline container has no pytest-cov wheel — skip with a
# note rather than fail (the suites themselves gated in tier-1 above).
if python -c "import pytest_cov" 2>/dev/null; then
    python -m pytest -q --cov=repro.nocsim --cov-fail-under=90 \
        tests/test_nocsim.py tests/test_nocsim_invariants.py \
        tests/test_nocsim_differential.py tests/test_golden_regression.py
else
    echo "pytest-cov unavailable (offline container without a wheel);"
    echo "coverage floor skipped — the nocsim suites ran uninstrumented in tier-1"
fi

echo "== dry-run artifacts (§Dry-run / §Roofline) =="
# Resumable: committed artifacts/dryrun/*.json cells are read back, only
# missing/failed cells recompile (minutes each on an empty dir).  Offline- and
# jax-version-tolerant: a failing sweep downgrades to a warning — the report
# still renders from whatever records are committed.
if [[ "${VERIFY_SKIP_DRYRUN:-0}" == "1" ]]; then
    echo "skipped (VERIFY_SKIP_DRYRUN=1)"
elif python -m repro.launch.dryrun --all --out artifacts/dryrun; then
    echo "dry-run records complete (artifacts/dryrun)"
else
    echo "WARNING: dry-run sweep incomplete on this container; §Dry-run/"
    echo "         §Roofline render from the committed artifacts/dryrun records"
fi
if [[ "${VERIFY_SKIP_DRYRUN:-0}" != "1" ]]; then
    # artifacts/dryrun is version-controlled evidence: keep only status=ok
    # digests in it (a failing cell's traceback record must not be commit
    # bait; the resumable sweep retries non-ok cells anyway).
    python - <<'EOF'
import glob, json, os
for f in glob.glob("artifacts/dryrun/*.json"):
    if json.load(open(f)).get("status") != "ok":
        os.remove(f)
        print(f"removed failed dry-run record {f} (kept out of the evidence dir)")
EOF
fi

echo "VERIFY OK"
