"""The driver artifact contract (VERDICT r4 #1).

The driver that scores bench.py keeps only a bounded tail of stdout and
parses the LAST line.  Rounds 1-4 all scored ``parsed=null`` because the
final line was the full ~10 KB payload and the bounded tail truncated its
head.  The contract now is: every emission prints the full payload line
followed by a compact (≤1 KB) summary line, so the last retained line is
always complete JSON regardless of where the tail window cuts.

What the line may NOT carry (ISSUE 21): a number this run did not
measure.  A zero-section run publishes ``value: null``, and a run that
finds no TPU without an explicit ``BENCH_FORCE_CPU=1`` exits non-zero.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def _fat_payload():
    """A payload strictly larger than any real run produces (~40 KB)."""
    detail = {
        "platform": "tpu",
        "scale": 1.0,
        "sections_done": ["sync_floor", "rank", "match", "driver_cycle",
                          "fused_cycle", "store_cycle", "match_large",
                          "rebalance", "end2end", "pallas_scale",
                          "pipeline", "placement_quality"],
    }
    for i in range(500):
        detail[f"section_metric_{i}"] = {"p50_ms": 123.456, "p99_ms": 789.0,
                                         "samples": list(range(20))}
    return {
        "metric": "match_cycle_p99_ms_rank1M_match1kx50k",
        "value": 232.1,
        "unit": "ms",
        "vs_baseline": 4.56,
        "detail": detail,
        "error": "x" * 5000,
    }


def test_compact_payload_is_under_1kb_and_carries_headline():
    out = bench.compact_payload(_fat_payload())
    line = json.dumps(out)
    assert len(line) <= bench.COMPACT_MAX_BYTES
    parsed = json.loads(line)
    assert parsed["metric"] == "match_cycle_p99_ms_rank1M_match1kx50k"
    assert parsed["value"] == 232.1
    assert parsed["unit"] == "ms"
    assert parsed["vs_baseline"] == 4.56
    assert parsed["platform"] == "tpu"
    assert parsed["scale"] == 1.0
    assert parsed["sections_done"]  # list of names or a count, never absent


def test_compact_payload_survives_corrupt_value():
    """An arbitrary structure leaking into ``value`` must still come out
    ≤1 KB and parseable."""
    p = _fat_payload()
    p["value"] = {"oops": ["x" * 100] * 50}  # ~5 KB structure
    out = bench.compact_payload(p)
    line = json.dumps(out)
    assert len(line) <= bench.COMPACT_MAX_BYTES
    assert json.loads(line)["value"] is None  # non-numeric value dropped


def test_compact_payload_minimal_payload():
    out = bench.compact_payload({"metric": "m", "value": None, "unit": "ms",
                                 "vs_baseline": None})
    line = json.dumps(out)
    assert len(line) <= bench.COMPACT_MAX_BYTES
    assert json.loads(line)["value"] is None


def test_build_payload_records_sections_done():
    payload = bench.build_payload(
        {"rank": None, "sync_floor": {"sync_floor_ms": 1.0}},
        {"sync_floor": "cpu"}, {"rank": "boom"}, 0.0)
    assert payload["detail"]["sections_done"] == ["sync_floor"]


def test_driver_bounded_tail_parses_last_line():
    """Simulated driver: run bench.py end-to-end (no sections, forced CPU —
    the emission path is identical), retain only the final 4 KB of stdout,
    and require the last retained line to be complete JSON with the
    headline fields."""
    env = dict(os.environ)
    env.update({"BENCH_FORCE_CPU": "1", "BENCH_SECTIONS": "none",
                "JAX_PLATFORMS": "cpu"})
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    tail = p.stdout[-4096:]  # the driver's bounded tail
    last = tail.strip().splitlines()[-1]
    assert len(last) <= bench.COMPACT_MAX_BYTES
    parsed = json.loads(last)
    for key in ("metric", "value", "unit", "vs_baseline", "platform",
                "scale", "sections_done"):
        assert key in parsed, f"missing {key}: {last}"
    # a zero-section run measured nothing and says so: no committed
    # number is ever published in its place
    assert parsed["value"] is None
    # second-to-last line is the full payload, also valid JSON
    full = json.loads(p.stdout.strip().splitlines()[-2])
    assert full["metric"] == parsed["metric"]
    assert "detail" in full


def test_no_chip_and_no_forced_cpu_is_an_error():
    """No TPU and no explicit BENCH_FORCE_CPU=1: non-zero exit, and the
    last line still parses with a null value and the reason."""
    env = dict(os.environ)
    env.pop("BENCH_FORCE_CPU", None)
    env.update({"BENCH_SECTIONS": "none", "JAX_PLATFORMS": "cpu",
                "BENCH_PROBE_ATTEMPTS": "1"})
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert p.returncode != 0
    parsed = json.loads(p.stdout.strip().splitlines()[-1])
    assert parsed["value"] is None
    assert "no TPU" in parsed["error"]
