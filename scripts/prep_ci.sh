#!/usr/bin/env bash
# Device-resident request-plane CI lane (PR 17): pin on-device prep
# and HOCL-style write combining on the CPU mesh.
#
# Runs (1) the request-plane fast tier (host-vs-device staged-input
# bit-identity across the sentinel-padding shape classes, the
# u64_shr_dyn dynamic-shift twin, write-combining bit-identity
# including a host-held lock inside a combined group and a fresh-leaf
# split burst, exactly-once acks + journal-order replay under
# combining, the sealed zero-retrace pin with BOTH knobs armed, knob
# parsing, and the perfgate prep-placement comparability wall), and
# (2) the host-vs-device A/B driver end to end: chained-delta prep
# walls for both impls and a measured combine ratio > 0, with the
# JSON receipt shape bench rounds consume.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

echo "== request-plane fast tier (prep bit-identity, combining, zero-retrace) =="
python -m pytest tests/test_prep.py -q

echo "== combining fuzz round (exactly-once ledger across torn-tail replay) =="
python -m pytest "tests/test_fuzz.py::test_fuzz_client_contract_write_combine" \
    -q -m ''

echo "== host-vs-device prep A/B driver (receipt shape + combine ratio) =="
KEYS=4000 W=512 K=2 DUP=8 python tools/profile_prep.py > /tmp/_prep_ci.json
python - <<'EOF'
import json
d = json.loads(open("/tmp/_prep_ci.json").read().strip().splitlines()[-1])
assert d["metric"] == "prep_ab"
assert set(d["impls"]) == {"host", "device"}
for impl, row in d["impls"].items():
    assert row["prep_ms"] >= 0 and row["step_ms"] > 0, (impl, row)
assert d["combine"]["locks_saved"] > 0, (
    f"duplicate-leaf batch never combined: {d['combine']}")
assert 0 < d["combine"]["ratio"] <= 1, d["combine"]
print("prep A/B:", d["impls"]["host"]["prep_ms"], "ms host vs",
      d["impls"]["device"]["prep_ms"], "ms device (CPU-mesh walls);",
      "combine ratio", d["combine"]["ratio"])
EOF
echo "== perfgate: live receipt with default request-plane stamps stays green =="
python - <<'EOF'
import json, os, subprocess, sys, tempfile
d = json.load(open("tests/data/perfgate/BENCH_r05.json"))["parsed"]
cfg = dict(d.get("config") or {})
tmp = tempfile.mkdtemp(prefix="prep_ci_")

# bench.py now stamps the request-plane knobs; a default-knob receipt
# (prep_impl=host, write_combine off) must gate exactly like the
# pre-stamp rounds (absent field == the host fact).
d["config"] = dict(cfg, prep_impl="host", write_combine=False)
p = os.path.join(tmp, "stamped.json")
json.dump(d, open(p, "w"))
rc = subprocess.run([sys.executable, "tools/perfgate.py",
                     "--receipt", p, "--repo",
                     "tests/data/perfgate"]).returncode
assert rc == 0, f"default-stamp receipt must stay green (rc={rc})"

# device placement is incomparable config: the wall must hold on the
# live trajectory (exit 2 = no comparable metric, never a false red).
d["config"] = dict(cfg, prep_impl="device", write_combine=False)
p = os.path.join(tmp, "device.json")
json.dump(d, open(p, "w"))
rc = subprocess.run([sys.executable, "tools/perfgate.py",
                     "--receipt", p, "--repo",
                     "tests/data/perfgate"]).returncode
assert rc == 2, f"device-placement receipt must be incomparable (rc={rc})"
print("perfgate: default stamps green, device placement walled")
EOF
echo "PREP-CI PASS"
