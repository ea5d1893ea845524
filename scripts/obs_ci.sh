#!/usr/bin/env bash
# Observability CI lane: pin the SLO + device telemetry planes on the
# CPU mesh.
#
# Runs (1) the obs + slo + device fast tier (registry
# snapshot-vs-increment fuzz, Chrome-trace schema, per-op-class SLO
# trackers + engine wiring, flight recorder, Prometheus exposition,
# perfgate pass/flag pins, the obs-on/off staged-wall < 2% cost pins
# for BOTH planes, compile-ledger seal/retrace semantics), (2) the
# flight-recorder drill: the chaos drill with the black box armed — the
# dump must contain the injected fault, the degraded transition and the
# recovery step IN ORDER (the drill asserts it and the receipt records
# it), (3) the perf-regression gate: green against the committed r05
# receipt, RED against a synthetically degraded (-20%) one — the gate
# is pinned in both directions so it can neither rot green nor cry
# wolf, (4) the device plane's two pins: the ZERO-RETRACE steady-state
# pin (tools/device_report.py's sealed read-only loop, aligned AND
# pipelined — warmup must compile every program variant exactly once,
# any compile inside the sealed window fails the report) and the
# SYNTHETIC-RETRACE pin (a receipt whose ledger counted a retrace must
# go red in perfgate, hard, no margin), and (5) the device_report
# driver smoke (live + --receipt renderer, rides the slow tier).
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

echo "== obs + slo + device fast tier =="
python -m pytest tests/test_obs.py tests/test_slo.py \
    tests/test_device_obs.py -q

echo "== flight-recorder drill (black box must show inject -> degrade -> recover) =="
BB_DIR=$(mktemp -d)/blackbox
SHERMAN_BLACKBOX_DIR="$BB_DIR" \
    python bench.py --chaos-drill --keys "${SHERMAN_DRILL_KEYS:-3000}"
ls "$BB_DIR"/blackbox-*.json >/dev/null
python - "$BB_DIR" <<'EOF'
import glob, json, sys
dump = sorted(glob.glob(sys.argv[1] + "/blackbox-*-chaos_drill.json"))[-1]
evs = json.load(open(dump))["otherData"]["flight_events"]
seq = {}
for k in ("chaos.inject", "engine.degraded_enter", "checkpoint.restore"):
    seq[k] = next(e["seq"] for e in evs if e["kind"] == k)
assert seq["chaos.inject"] < seq["engine.degraded_enter"] \
    < seq["checkpoint.restore"], seq
print("black box ordered:", seq)
EOF

echo "== perf gate: green on its synthetic r05 receipt =="
python tools/perfgate.py --receipt tests/data/perfgate/BENCH_r05.json \
    --repo tests/data/perfgate

echo "== perf gate: RED on a -20% degraded receipt =="
python - <<'EOF'
import json, os, subprocess, sys, tempfile
d = json.load(open("tests/data/perfgate/BENCH_r05.json"))["parsed"]
for k in ("value", "client_ops_s", "sustained_ops_s", "sus_mixed_ops_s"):
    if d.get(k):
        d[k] = round(d[k] * 0.8)
p = os.path.join(tempfile.mkdtemp(prefix="perfgate_ci_"), "degraded.json")
json.dump(d, open(p, "w"))
rc = subprocess.run([sys.executable, "tools/perfgate.py",
                     "--receipt", p, "--repo",
                     "tests/data/perfgate"]).returncode
assert rc == 1, f"perfgate must flag a -20% receipt (rc={rc})"
print("degraded receipt flagged (rc=1)")
EOF

echo "== device plane: zero-retrace steady-state pin (aligned) =="
# device_report's sealed loop raises if ANY program compiles inside
# the steady-state window — the pin that warmup covers every variant
KEYS=20000 B=8192 DEVB=8192 K=2 STEPS=6 FUSION=aligned \
    python tools/device_report.py > /dev/null

echo "== device plane: zero-retrace steady-state pin (pipelined) =="
KEYS=20000 B=8192 DEVB=8192 K=2 STEPS=6 FUSION=pipelined \
    python tools/device_report.py > /dev/null

echo "== device plane: synthetic-retrace pin is RED =="
python - <<'EOF'
import json, os, subprocess, sys, tempfile
d = json.load(open("tests/data/perfgate/BENCH_r05.json"))["parsed"]
d["device"] = {"ledger": {"retraces": 1}}
p = os.path.join(tempfile.mkdtemp(prefix="perfgate_ci_"), "retrace.json")
json.dump(d, open(p, "w"))
rc = subprocess.run([sys.executable, "tools/perfgate.py",
                     "--receipt", p, "--repo",
                     "tests/data/perfgate"]).returncode
assert rc == 1, f"perfgate must flag a steady-state retrace (rc={rc})"
print("retraced receipt flagged (rc=1)")
EOF

echo "== device_report driver smoke (live + receipt renderer) =="
python -m pytest "tests/test_tools.py::test_device_report_driver" \
    -q -m ''
echo "OBS-CI PASS"
