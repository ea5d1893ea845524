#!/usr/bin/env bash
# Kernel data-plane CI lane: pin the explicit-DMA page engine on the
# CPU mesh.
#
# Runs (1) the pallas_page parity fuzz + engine-level pool bit-identity
# pin (including the slow 4-node form), (2) the transport_pallas
# exchange parity + typed-error coverage, (3) the v5e compiles of every
# kernel at real widths, and (4) the tools/profile_gather.py driver smoke — the same chained-delta
# harness whose chip capture decides the gather_impl knob
# (BENCHMARKS.md "Chip-session queue").
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

echo "== page-kernel parity fuzz (incl. slow tier) =="
python -m pytest tests/test_pallas_page.py -q -m ''

echo "== transport pallas exchange + typed errors =="
python -m pytest tests/test_transport_pallas.py -q

echo "== v5e compiles at real widths (described topology) =="
python -m pytest tests/test_tpu_compile.py -q

echo "== profile_gather driver smoke (interpreted mechanics) =="
python -m pytest tests/test_tools.py::test_profile_gather_driver -q

echo "KERNELS-CI PASS"
