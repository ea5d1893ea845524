#!/usr/bin/env python
"""Perf-regression gate over the committed BENCH_r*.json trajectory.

The published throughput trajectory (``BENCH_r*.json`` in ``--repo``,
the repo root by default) is the contract every perf PR must not
silently regress.  This tool
parses the committed rounds plus a fresh receipt, applies NOISE-AWARE
thresholds, and exits nonzero on regression — the CI lane
(``scripts/obs_ci.sh``) runs it against the synthetic trajectory in
``tests/data/perfgate`` so the gate itself is pinned green on
known-good data, and against a
synthetically degraded receipt so it is pinned RED on a real loss.

Noise calibration: a pre-PR-1 round-5 capture (git history at 2d6a76f)
measured 33.8 M ops/s in the log and 32.2 M in the JSON for the SAME
configuration minutes apart — a ~5% same-build run spread.  The gate
anchors on it until chip runs of this tree measure their own spread.
The default margin is ``max(--min-margin,
--spread-mult x max(calibrated spread, observed cross-round spread))``
per metric: with the defaults (min 10%, mult 2.0) a -20% sustained
loss FAILS while the r05-vs-r05 and r02-r05 cross-round wiggles (~1-7%)
PASS.

Comparability rules (the trajectory's own lessons):

- only rounds with the same ``keys`` and ``batch`` as the candidate
  compare (r01's retracted 107 M predates the accounting and carries
  no config — it filters itself out);
- a NODE-COUNT change is incomparable config: an elastic reshard
  (``bench.py --reshard-drill``, ``sherman_tpu/migrate.py``) changes
  the per-node workload and the exchange topology wholesale, so a
  receipt captured at M nodes never gates against a round captured at
  N != M — reshard-drill receipts themselves carry their own metric
  (``reshard_drill``) and are not bench receipts at all (feeding one
  here exits 2: no comparable metric).  Rounds predating the ``nodes``
  field compare as 1-node runs — ``bench.py`` hardcoded
  ``machine_nr=1`` for the whole committed trajectory, so the default
  is a fact, not a guess;
- ``sustained_ops_s`` compares only between device-staged runs (both
  sides must carry ``sus_dev_ms_per_step``): r04's host-shipped 3.9 M
  is a different methodology and must never become the baseline;
- the hot-key leaf cache (the optional schema-3 ``cache`` block) is
  comparable-config metadata: a cache-ON receipt's ``sustained_ops_s``
  never gates against a cache-OFF round's and vice versa — most ops of
  a cache-ON loop never descend, a different workload per step;
- a VALUE-CONFIG change is incomparable config (PR 14): rows whose
  ``config.value_bytes`` / ``config.value_dist`` / ``config.value_heap``
  differ never gate against each other — out-of-line heap reads gather
  payload pages inline reads never touch, and payload size rescales
  every byte-bound phase.  Receipts predating the fields compare as
  fixed-width 8-byte inline (the hardcoded pre-heap fact), so the
  committed trajectory keeps gating;
- SERVE-MODE receipts (``tools/serve_bench.py`` / ``bench.py --serve``
  — the open-loop, admission-paced front door; identified by the
  ``serve`` block or ``metric == "serve_bench"``) are a different
  methodology wholesale: a front-door receipt NEVER gates against a
  closed-loop round's ``sustained_ops_s`` (or any other closed-loop
  metric) and vice versa — an open loop pays admission pacing,
  queueing and per-request acks the closed loop does not, so the
  comparison would manufacture regressions both ways.  WITHIN
  serve-mode rounds, per-class p99 (``serve_read_p99_ms`` /
  ``serve_write_p99_ms``, lower-is-better) and open-loop throughput
  (``serve_ops_s``) gate with the same noise-margin rule — but only
  between rounds whose ``serve.p99_targets_ms`` match: a target change
  re-aims the adaptive controller, which is a config change, not a
  regression;
- CLIENT-CONTRACT receipts (``tools/contract_drill.py``, metric
  ``contract_drill``) are robustness artifacts, never throughput-gated
  — but their pins are HARD reds with no margin (the retrace-red
  pattern): ``duplicate_acks > 0``, ``lost_acks > 0`` or
  ``linearizable == false`` in a committed receipt fails the gate
  outright; with the pins green the receipt passes on them alone
  (no comparable throughput metric required);
- REPLICATION (PR 16) is incomparable config: a receipt with the
  replication plane ON (a ``repl`` block, a ``replicas`` config, or
  metric ``failover_drill``) never throughput-gates against
  unreplicated rounds — the follower tier re-applies every journaled
  write R more times in the same process.  Failover-drill receipts
  carry the same marginless hard-red pins as contract receipts
  (``lost_acks`` / ``duplicate_acks`` / ``linearizable``);
- QUORUM ACKS (PR 18) are incomparable config: a receipt whose
  effective ``ack_quorum`` differs (the ``repl.quorum.ack_quorum`` /
  ``config.ack_quorum`` field; missing = 1, the shipped primary-only
  default) never throughput-gates in EITHER direction — a
  quorum-gated ack waits on follower durability the primary-only ack
  never pays, and comparing the other way would launder the wait as a
  win.  Partition-drill receipts (``tools/partition_drill.py``,
  metric ``partition_drill``) carry the contract hard-red pins plus
  two of their own, ``fenced_acks_merged > 0`` and
  ``diverged_followers_unrepaired > 0`` — each a zero-tolerance
  split-brain/divergence verdict, marginless;
- a HOST-COUNT change is incomparable config (PR 19): rows whose
  ``config.hosts`` differ (missing = 1, the pre-multihost fact) never
  throughput-gate in either direction — N per-host journal streams
  fsync in parallel and N front doors admit independently, so a
  multihost number is a different service plane, not a faster one.
  Multihost-drill receipts (``tools/multihost_drill.py``, metric
  ``multihost_drill``) carry the contract hard-red pins plus
  ``rpo_ops > 0`` — an acked op missing after union recovery is lost
  durability, marginless; the drill's ack-bandwidth speedup is
  published in the receipt, never gated here against hosts=1 rounds;
- a PREP-PLACEMENT change is incomparable config (PR 17): rows whose
  ``config.prep_impl`` or ``config.write_combine`` differ never
  throughput-gate against each other — host prep serializes
  ``np.unique``/sort/route wall clock into every step that device prep
  moves onto the chip, and write combining changes the lock-acquisition
  count per batch wholesale.  Receipts predating the fields compare as
  ``("host", False)`` (the hardcoded pre-PR-17 fact), so the committed
  trajectory keeps gating;
- a metric missing on either side is skipped, not failed — but a
  candidate with NO comparable metric at all exits 2 (the gate cannot
  vouch for it).

White-box device gates (schema_version 3, the ``device`` section): a
candidate carrying the compile ledger goes RED on ``retraces > 0`` —
bench.py seals the ledger around every timed window, so any counted
retrace is a real steady-state recompile, a hard fail with no margin —
and on an ``achieved_bytes_frac`` drop beyond the noise-margin rule on
any roofline phase both sides publish.  Rounds without a ``device``
section (schema 1/2, r01-r07) simply skip the device gates — older
artifacts stay comparable on the throughput metrics, never crash the
gate.

Usage::

    python tools/perfgate.py --receipt tests/data/perfgate/BENCH_r05.json \
        --repo tests/data/perfgate                           # pass pin
    python tools/perfgate.py --receipt fresh.json            # gate a run
    python tools/perfgate.py --receipt f.json --json         # receipt only

Receipts may be the driver-wrapped form (``{"n": .., "parsed": {...}}``
— the committed BENCH_r*.json shape) or a bare bench JSON line.  Exit
codes: 0 pass, 1 regression, 2 unusable input.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

# round-5 same-config run spread: 33.8 M (log) vs 32.2 M (JSON) — the
# measured single-build noise floor this gate's thresholds anchor on
CALIBRATED_SPREAD = 33.8 / 32.2 - 1.0  # ~0.050

# watched metrics: (name, higher_is_better)
METRICS = (
    ("value", True),             # headline client ops/s
    ("sustained_ops_s", True),   # device-staged open loop (r05+)
    ("sus_mixed_ops_s", True),   # YCSB-A mixed loop
    ("p99_ms", False),           # step-span tail latency
    # serve-mode metrics (r12+, gate only within serve-mode rounds at
    # matching p99 targets — see the comparability rules)
    ("serve_ops_s", True),       # open-loop front-door throughput
    ("serve_read_p99_ms", False),   # end-to-end per-request read p99
    ("serve_write_p99_ms", False),  # end-to-end per-request write p99
)


def load_receipt(path: str) -> dict:
    """One receipt: driver-wrapped ({"parsed": {...}}) or bare."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        parsed = dict(doc["parsed"])
        parsed.setdefault("_round", doc.get("n"))
        return parsed
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a bench receipt")
    return doc


def load_trajectory(repo: str) -> list[dict]:
    """Committed BENCH_r*.json receipts, ascending by round."""
    rounds = []
    for p in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if not m:
            continue
        try:
            r = load_receipt(p)
        except (ValueError, json.JSONDecodeError):
            continue
        r["_round"] = int(m.group(1))
        r["_path"] = p
        rounds.append(r)
    return sorted(rounds, key=lambda r: r["_round"])


def _device_fracs(r: dict) -> dict:
    """``{group.phase: achieved_bytes_frac}`` from a schema-3 receipt's
    ``device.rooflines`` block; {} when the section (or the fraction —
    unknown-peak devices publish absolute rates only) is absent."""
    dev = r.get("device")
    if not isinstance(dev, dict):
        return {}
    out = {}
    for group, phases in (dev.get("rooflines") or {}).items():
        if not isinstance(phases, dict):
            continue
        for phase, rec in phases.items():
            f = (rec.get("achieved_bytes_frac")
                 if isinstance(rec, dict) else None)
            if isinstance(f, (int, float)) and f > 0:
                out[f"{group}.{phase}"] = float(f)
    return out


def _cache_on(r: dict) -> bool:
    """True when the receipt's device-staged loop ran with the hot-key
    leaf cache enabled (the optional schema-3 ``cache`` block; absent
    block = cache off — every pre-cache round)."""
    c = r.get("cache")
    return bool(isinstance(c, dict) and c.get("enabled"))


def _value_cfg(r: dict) -> tuple:
    """The receipt's value configuration (config.value_bytes /
    value_dist / value_heap, PR 14).  Absent fields = the pre-heap
    fact: every committed round ran fixed-width 8-byte inline values
    (bench.py hardcoded them until the fields existed), so older
    artifacts compare as (8, "fixed", False) rather than skipping."""
    c = r.get("config") or {}
    return (c.get("value_bytes") or 8,
            c.get("value_dist") or "fixed",
            bool(c.get("value_heap")))


def _prep_cfg(r: dict) -> tuple:
    """The receipt's request-plane placement (config.prep_impl /
    write_combine, PR 17).  Absent fields = the pre-PR-17 fact: every
    committed round ran host prep with combining off (both knobs ship
    OFF and the fields didn't exist), so older artifacts compare as
    ("host", False) rather than skipping."""
    c = r.get("config") or {}
    return (c.get("prep_impl") or "host", bool(c.get("write_combine")))


def _serve_mode(r: dict) -> bool:
    """True for a serving-front-door receipt (open-loop, admission-
    paced — ``tools/serve_bench.py``): the ``serve`` block or the
    ``serve_bench`` metric name.  Serve-mode and closed-loop receipts
    never gate against each other (different methodology wholesale —
    see the module docstring's comparability rules)."""
    return bool(isinstance(r.get("serve"), dict)
                or r.get("metric") == "serve_bench")


def _replicated(r: dict) -> bool:
    """A receipt ran with the replication plane ON: a ``repl`` block
    (the ReplicaGroup's receipt), a follower count in its config, or
    the failover-drill metric itself.  Missing everything = the
    unreplicated fact (replication is OFF by default), so the whole
    committed trajectory keeps comparing."""
    if isinstance(r.get("repl"), dict) \
            or r.get("metric") in ("failover_drill",
                                   "partition_drill"):
        return True
    return bool(r.get("replicas")
                or (r.get("config") or {}).get("replicas"))


def _quorum_cfg(r: dict) -> int:
    """The receipt's effective ``ack_quorum`` (PR 18).  Missing
    everywhere = 1, the shipped primary-only default — so the whole
    committed trajectory keeps comparing.  Quorum-gated rounds wait
    on follower durability per ack; they never throughput-gate
    against primary-only rounds in either direction."""
    q = (r.get("repl") or {}).get("quorum")
    if isinstance(q, dict) and q.get("ack_quorum"):
        return int(q["ack_quorum"])
    return int(r.get("ack_quorum")
               or (r.get("config") or {}).get("ack_quorum")
               or (r.get("serve") or {}).get("ack_quorum") or 1)


def _hosts_cfg(r: dict) -> int:
    """The receipt's host count (config.hosts, PR 19).  Absent
    everywhere = 1, the pre-multihost fact: every committed round ran
    one host's front door and one journal stream — so the whole
    committed trajectory keeps comparing.  A multihost round fsyncs N
    journal streams in parallel and admits through N width
    controllers; its numbers never gate against single-host rounds in
    either direction (the PR 12 ``nodes`` rule's pattern)."""
    return int((r.get("config") or {}).get("hosts")
               or r.get("hosts") or 1)


def _comparable(cand: dict, r: dict, metric: str) -> bool:
    if r.get("keys") != cand.get("keys") \
            or r.get("batch") != cand.get("batch"):
        return False
    # serve-mode wall: front-door receipts gate only within serve-mode
    # rounds, closed-loop receipts only within closed-loop rounds
    if _serve_mode(cand) != _serve_mode(r):
        return False
    # replication wall (PR 16): a replicated round's follower tier
    # re-applies every journaled write R more times in the same
    # process — its walls and throughputs never gate against
    # unreplicated rounds (and vice versa)
    if _replicated(cand) != _replicated(r):
        return False
    # quorum-ack wall (PR 18): differing effective ack_quorum never
    # gates in either direction — the K>1 ack pays a follower-
    # durability wait the primary-only ack does not
    if _quorum_cfg(cand) != _quorum_cfg(r):
        return False
    if metric.startswith("serve_"):
        # per-class p99 gates only between rounds aiming at the SAME
        # targets — a re-aimed controller is a config change
        if (cand.get("serve") or {}).get("p99_targets_ms") \
                != (r.get("serve") or {}).get("p99_targets_ms"):
            return False
    # node-count rule (see the docstring): a reshard changes the
    # per-node workload — different node counts never compare.  A
    # receipt without the field ran machine_nr=1 (the pre-field
    # bench.py hardcoded it).
    if (r.get("nodes") or 1) != (cand.get("nodes") or 1):
        return False
    # host-count rule (PR 19): differing host counts never compare —
    # N per-host journal streams ack in parallel and N front doors
    # admit independently, so a multihost number is a different
    # service plane, not a faster one.  Missing field = hosts=1 (the
    # pre-multihost fact), so the committed trajectory keeps
    # comparing.
    if _hosts_cfg(r) != _hosts_cfg(cand):
        return False
    # value-config rule (PR 14): rows with differing value_bytes /
    # value_dist / value_heap never gate against each other — an
    # out-of-line heap read gathers payload pages the inline read never
    # touches, and a payload-size change rescales every byte-bound
    # phase.  Missing fields = the pre-heap inline fact (see
    # _value_cfg), so the whole committed trajectory keeps comparing.
    if _value_cfg(r) != _value_cfg(cand):
        return False
    # prep-placement rule (PR 17): differing config.prep_impl or
    # config.write_combine never gate against each other — host prep
    # pays np.unique/sort/route wall clock device prep doesn't, and
    # combining changes locks-per-batch wholesale.  Missing fields =
    # ("host", False), the pre-field fact (see _prep_cfg), so the
    # committed trajectory keeps comparing.
    if _prep_cfg(r) != _prep_cfg(cand):
        return False
    if r.get(metric) is None or cand.get(metric) is None:
        return False
    if metric == "sustained_ops_s":
        # device-staged methodology on BOTH sides (r04's host-shipped
        # sustained number is not this metric's baseline)
        if not r.get("sus_dev_ms_per_step") \
                or not cand.get("sus_dev_ms_per_step"):
            return False
        # hot-key-cache comparability: the ``cache`` block is
        # comparable-config METADATA, not a gated number — a cache-ON
        # sustained loop serves most ops without descending, so it
        # never gates against a cache-OFF round (and vice versa; the
        # same rule as device-staged-vs-device-staged above)
        if _cache_on(r) != _cache_on(cand):
            return False
    return True


def _margin_entry(val: float, comp: list[tuple], higher: bool, *,
                  spread_mult: float, min_margin: float) -> dict:
    """One metric's noise-margin verdict from its ``(round, value)``
    history: baseline = the latest comparable round, margin =
    max(min_margin, spread_mult * max(calibrated, observed cross-round
    spread)).  Shared by the throughput/wall loop and the device
    bytes-frac gate so the two noise rules can't drift apart."""
    base_round, baseline = comp[-1]
    vals = [v for _, v in comp]
    observed_spread = (max(vals) / min(vals) - 1.0) \
        if min(vals) > 0 and len(vals) > 1 else 0.0
    margin = max(min_margin,
                 spread_mult * max(CALIBRATED_SPREAD, observed_spread))
    ratio = val / baseline if baseline else 1.0
    ok = ratio >= 1.0 - margin if higher else ratio <= 1.0 + margin
    return {
        "candidate": val,
        "baseline": baseline,
        "baseline_round": base_round,
        "ratio": round(ratio, 4),
        "margin": round(margin, 4),
        "observed_spread": round(observed_spread, 4),
        "direction": "higher" if higher else "lower",
        "ok": ok,
    }


def gate(cand: dict, rounds: list[dict], *, spread_mult: float = 2.0,
         min_margin: float = 0.10) -> dict:
    """-> {"ok": bool, "metrics": {name: {...}}, ...}; pure function of
    the receipts so tests can drive it directly."""
    out: dict = {"metric": "perfgate", "ok": True, "metrics": {},
                 "calibrated_spread": round(CALIBRATED_SPREAD, 4),
                 "spread_mult": spread_mult, "min_margin": min_margin}
    # never gate a committed round against itself: a receipt carrying a
    # round number (the driver-wrapped BENCH_rNN form) is compared to
    # the rounds BEFORE it; a bare fresh receipt gates on the full
    # trajectory
    cand_round = cand.get("_round")
    history = [r for r in rounds
               if cand_round is None or r["_round"] < cand_round]
    for name, higher in METRICS:
        comp = [r for r in history if _comparable(cand, r, name)]
        if not comp:
            out["metrics"][name] = {"skipped": "no comparable round"}
            continue
        entry = _margin_entry(
            float(cand[name]),
            [(r["_round"], float(r[name])) for r in comp],
            higher, spread_mult=spread_mult, min_margin=min_margin)
        out["metrics"][name] = entry
        if not entry["ok"]:
            out["ok"] = False
    # the comparability contract is about the THROUGHPUT trajectory:
    # device gates below are self-contained extras and must not rescue
    # a receipt no committed round can vouch for
    gated = [n for n, d in out["metrics"].items() if "ok" in d]
    out["gated_metrics"] = gated
    if not gated:
        out["ok"] = False
        out["error"] = ("no comparable metric between the candidate and "
                        "the committed trajectory (keys/batch mismatch?)")

    # -- white-box device gates (schema_version 3 "device" section) ----------
    dev = cand.get("device")
    if isinstance(dev, dict):
        # steady-state retraces: bench.py seals the compile ledger
        # around every timed window, so ANY counted retrace is a real
        # silent recompile in steady state — a hard red, no noise
        # margin (it is a count of a hazard, not a wall)
        retr = int((dev.get("ledger") or {}).get("retraces", 0) or 0)
        rok = retr == 0
        out["metrics"]["device.retraces"] = {
            "candidate": retr, "baseline": 0, "direction": "zero",
            "ok": rok}
        out["gated_metrics"].append("device.retraces")
        if not rok:
            out["ok"] = False
        # achieved-bytes-fraction per published roofline phase: the
        # serve programs' fraction-of-peak must not silently sink.
        # Compare only against prior rounds that also publish the
        # fraction (schema >= 3 AND a known-peak device) at the same
        # keys/batch; everything older skips.
        hist_fracs = [(r, _device_fracs(r)) for r in history
                      if r.get("keys") == cand.get("keys")
                      and r.get("batch") == cand.get("batch")]
        cand_fracs = _device_fracs(cand)
        for name, val in sorted(cand_fracs.items()):
            comp = [(r["_round"], fr[name])
                    for r, fr in hist_fracs if name in fr]
            mkey = f"device.{name}.bytes_frac"
            if not comp:
                out["metrics"][mkey] = {
                    "skipped": "no comparable schema-3 round"}
                continue
            entry = _margin_entry(val, comp, True,
                                  spread_mult=spread_mult,
                                  min_margin=min_margin)
            out["metrics"][mkey] = entry
            out["gated_metrics"].append(mkey)
            if not entry["ok"]:
                out["ok"] = False
        # a fraction history published that the candidate DROPPED must
        # not pass silently — vanishing entirely is the limit of
        # "silently sinking".  A candidate publishing no fractions at
        # all skips instead (unknown-peak backend or cost analysis
        # unavailable wholesale: a platform difference, not a phase
        # regression).
        for name in sorted({n for _, fr in hist_fracs for n in fr}):
            if name in cand_fracs:
                continue
            mkey = f"device.{name}.bytes_frac"
            if not cand_fracs:
                out["metrics"][mkey] = {
                    "skipped": "candidate publishes no fractions"}
                continue
            base_round, baseline = [(r["_round"], fr[name])
                                    for r, fr in hist_fracs
                                    if name in fr][-1]
            out["metrics"][mkey] = {
                "candidate": None, "baseline": baseline,
                "baseline_round": base_round, "direction": "higher",
                "ok": False,
                "error": "fraction published by a committed round is "
                         "absent from the candidate",
            }
            out["gated_metrics"].append(mkey)
            out["ok"] = False

    # -- client-contract hard pins (PR 15): the retrace-red pattern ----------
    # Contract-drill receipts (tools/contract_drill.py) are ROBUSTNESS
    # artifacts: they carry no comparable throughput metric and must
    # never be throughput-gated — but a committed receipt claiming
    # `duplicate_acks > 0`, `lost_acks > 0` or `linearizable == false`
    # is a hard red with no margin: each is a count/verdict of a
    # correctness hazard, not a wall.
    if cand.get("metric") in ("contract_drill", "failover_drill",
                              "partition_drill", "multihost_drill",
                              "hostfail_drill") \
            or "duplicate_acks" in cand or "linearizable" in cand \
            or "fenced_acks_merged" in cand \
            or "unadopted_dead_hosts" in cand:
        # partition-drill pins (PR 18) ride the same marginless rule:
        # a merged fenced ack or an unrepaired diverged follower is a
        # split-brain/divergence verdict, not a wall; the multihost
        # drill (PR 19) adds rpo_ops — an acked op missing after
        # union recovery is lost durability, not a slow number; the
        # hostfail drill (PR 20) adds unadopted_dead_hosts — an
        # expired host nobody adopted is unavailability, not a wall
        for name in ("duplicate_acks", "lost_acks", "rpo_ops",
                     "fenced_acks_merged",
                     "diverged_followers_unrepaired",
                     "unadopted_dead_hosts"):
            val = cand.get(name)
            if val is None:
                continue
            cok = int(val) == 0
            out["metrics"][f"contract.{name}"] = {
                "candidate": int(val), "baseline": 0,
                "direction": "zero", "ok": cok}
            out["gated_metrics"].append(f"contract.{name}")
            if not cok:
                out["ok"] = False
        lin = cand.get("linearizable")
        if lin is not None:
            lok = bool(lin)
            out["metrics"]["contract.linearizable"] = {
                "candidate": lok, "baseline": True,
                "direction": "true", "ok": lok}
            out["gated_metrics"].append("contract.linearizable")
            if not lok:
                out["ok"] = False
        # a contract receipt is judged by its pins, not by throughput
        # comparability: clear the no-comparable-metric error (which
        # would exit 2) and let the pins decide pass/red.  Other
        # robustness receipts (reshard/recovery) still exit 2 here by
        # design — they carry no gateable claim at all.
        contract_gates = [m for m in out["gated_metrics"]
                          if m.startswith("contract.")]
        if out.get("error") and contract_gates:
            out.pop("error")
            out["ok"] = all(out["metrics"][m]["ok"]
                            for m in out["gated_metrics"]
                            if "ok" in out["metrics"][m])

    # -- lint provenance (PR 9): warn, never gate --------------------------
    # bench.py stamps config.lint_clean (shermanlint verdict of the tree
    # the receipt ran from; optional — older schemas lack it).  A False
    # means the number came from a convention-violating tree: worth an
    # asterisk next to the receipt, but walls are walls — lint hygiene
    # must not mask or manufacture a perf regression.
    lint = (cand.get("config") or {}).get("lint_clean")
    if lint is False:
        out.setdefault("warnings", []).append(
            "receipt produced from a tree WITH shermanlint findings "
            "(config.lint_clean=false) — re-run `python "
            "tools/shermanlint.py` and re-capture before committing")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="noise-aware perf-regression gate over BENCH_r*.json")
    ap.add_argument("--receipt", required=True,
                    help="fresh bench JSON (bare line or driver-wrapped)")
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding the committed BENCH_r*.json trajectory")
    ap.add_argument("--spread-mult", type=float, default=2.0,
                    help="margin = max(min-margin, mult x spread)")
    ap.add_argument("--min-margin", type=float, default=0.10,
                    help="floor on the relative regression margin")
    ap.add_argument("--json", action="store_true",
                    help="print the receipt JSON only (no prose line)")
    a = ap.parse_args(argv)

    try:
        cand = load_receipt(a.receipt)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(json.dumps({"metric": "perfgate", "ok": False,
                          "error": f"unreadable receipt: {e}"}))
        return 2
    rounds = load_trajectory(a.repo)
    if not rounds:
        print(json.dumps({"metric": "perfgate", "ok": False,
                          "error": f"no BENCH_r*.json under {a.repo}"}))
        return 2
    res = gate(cand, rounds, spread_mult=a.spread_mult,
               min_margin=a.min_margin)
    print(json.dumps(res))
    if not a.json:
        for w in res.get("warnings", ()):
            print(f"# WARNING: {w}", file=sys.stderr)
        for n, d in res["metrics"].items():
            if "ratio" in d:
                print(f"# {n}: {d['candidate']:.6g} vs r"
                      f"{d['baseline_round']} {d['baseline']:.6g} "
                      f"(ratio {d['ratio']}, margin {d['margin']}, "
                      f"{'ok' if d['ok'] else 'REGRESSION'})",
                      file=sys.stderr)
            elif "error" in d:  # vanished device fraction
                print(f"# {n}: {d['error']} (baseline r"
                      f"{d['baseline_round']} {d['baseline']:.6g}, "
                      "REGRESSION)", file=sys.stderr)
            elif "ok" in d:  # marginless hard gates (device.retraces)
                print(f"# {n}: {d['candidate']} (must be "
                      f"{d['baseline']}, "
                      f"{'ok' if d['ok'] else 'REGRESSION'})",
                      file=sys.stderr)
            else:
                print(f"# {n}: skipped ({d['skipped']})", file=sys.stderr)
        print("PERFGATE " + ("PASS" if res["ok"] else "FAIL"),
              file=sys.stderr)
    if "error" in res:
        return 2
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
