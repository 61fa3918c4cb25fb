"""Every per-layer metric BENCHMARK.json names has its file and a reader
that loads, and the two agree; the CycleRecord readers on a hand-made
window."""

import json
import os

import run

ROOT = os.path.dirname(run.HERE)


def test_every_per_layer_metric_has_its_file_and_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    readers = run.load_readers(bench)
    assert set(readers) == {m["name"] for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for name, (read, spec, entry) in readers.items():
        assert callable(read)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (name, entry["unit"], entry["layer"], entry["moves"])
        assert entry["moves"] in e2e


def test_cycle_readers_on_a_window_with_one_stalled_cycle():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        readers = run.load_readers(json.load(f))
    ctx = {"cycles": [{"start": 0.0, "duration_ms": 100.0},
                      {"start": 1.1, "duration_ms": 110.0},
                      {"start": 2.2, "duration_ms": 700.0},
                      {"start": 3.9, "duration_ms": 120.0}],
           "window": (0.0, 4.0), "launched_in_window": 3000}
    value = lambda name: readers[name][0](ctx, readers[name][1])
    assert value("cycle_p50_ms") == 115.0           # the stall moves it not
    assert abs(value("cycle_period_ms") - 1300.0) < 1e-6
    assert abs(value("cycle_period_max_ms") - 1700.0) < 1e-6
    assert value("launches_per_s") == 750.0
    assert value("pack_ms") is None                 # nothing to read
