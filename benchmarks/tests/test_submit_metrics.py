"""The per-layer metrics of the submission's own account and of what runs
between cycles: each is in BENCHMARK.json BY NAME (not by its place in
``per_layer``), has its file, and names a reader that exists; the six
parts read the six phases that partition a submission's wall; and the
parent's shapes read as nothing, never as 0 (tests/
test_submission_accounting.py reads ``histogram_mean`` on a live
exposition)."""

import json
import os

import run

ROOT = os.path.dirname(run.HERE)
SUBMIT = ("submit_handler_ms", "submit_cpu_ms", "submit_store_lock_ms",
          "submit_notify_wait_ms", "submit_drain_ms",
          "submit_commit_wait_ms", "submit_offcpu_ms")
#: name -> (reader, layer, moves)
NEW = {
    **{name: ("histogram_mean", "rest ingress and store journal",
              "ttp_p50_ms") for name in SUBMIT},
    "cycle_notify_wait_ms": ("cycle_mean", "reconcile apply launch",
                             "cycle_ms"),
    "flush_audit_ms": ("cycle_mean", "cycle thread cadence", "ttp_p50_ms"),
    "gc_ms": ("cycle_mean", "cycle thread cadence", "ttp_p50_ms"),
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_each_new_metric_is_an_entry_a_file_and_a_reader_by_name():
    b = bench()
    entries = {m["name"]: m for m in b["per_layer"]}
    assert set(NEW) <= set(entries)
    older = {m["layer"] for m in b["per_layer"] if m["name"] not in NEW}
    e2e = {m["name"] for m in b["end_to_end"]}
    readers = run.load_readers(b)
    for name, (reader, layer, moves) in NEW.items():
        read, spec, entry = readers[name]
        assert callable(read)
        assert entry is entries[name]
        assert os.path.isfile(os.path.join(run.HERE, "layer_metrics",
                                           name + ".json"))
        assert spec["reader"] == reader
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (name, entry["unit"], entry["layer"], entry["moves"])
        assert (entry["layer"], entry["moves"]) == (layer, moves)
        assert entry["layer"] in older and entry["moves"] in e2e
        assert entry["better"] == "lower" and entry["unit"] == "ms"
        assert entry["source"] == "program_span"
        assert "workloads" not in entry        # every cell reports them


def test_the_submission_parts_read_the_phases_the_program_exports():
    from cook_tpu.rest import instrument
    readers = run.load_readers(bench())
    phases = {readers[n][1]["sum_labels"].get("phase") for n in SUBMIT}
    # the handler's wall reads the request histogram itself; the six
    # parts are the six phases that partition it
    assert phases - {None} == set(instrument.WAIT_PHASES) | {"cpu",
                                                             "offcpu"}
    assert set(instrument.WAIT_PHASES) | {"cpu", "offcpu"} \
        <= set(instrument.PHASE_SPANS)
    for name in SUBMIT:
        spec = readers[name][1]
        # denominated by the submissions: what JobClient.submit posts
        assert spec["count"] == "cook_http_request_duration_seconds"
        assert spec["count_labels"] == {"endpoint": "/jobs",
                                        "method": "POST"}


def test_the_parents_exposition_and_records_read_as_nothing():
    readers = run.load_readers(bench())
    # the parent: the requests' histogram and the older phases only
    post = 'endpoint="/jobs",method="POST"'
    before = {f"cook_http_request_duration_seconds_count{{{post}}}": 5.0,
              f"cook_http_request_duration_seconds_sum{{{post}}}": 0.02}
    after = {f"cook_http_request_duration_seconds_count{{{post}}}": 9.0,
             f"cook_http_request_duration_seconds_sum{{{post}}}": 0.04,
             f'cook_http_phase_seconds_sum{{{post},phase="journal.append"}}':
             0.001}
    ctx = {"metrics_before": before, "metrics_after": after,
           "cycles": [{"blocked_ms": {"store_lock": 0.0, "commit_wait": 1.0,
                                      "device": 0.2, "gc": 0.0},
                       "gc_ms": 0.0, "flush_audit_ms": 270.0}]}
    got = {name: readers[name][0](ctx, readers[name][1]) for name in NEW}
    assert got["submit_handler_ms"] == 5.0        # the parent has its wall
    for name in SUBMIT[1:] + ("cycle_notify_wait_ms",):
        assert got[name] is None, name
    assert got["flush_audit_ms"] == 270.0 and got["gc_ms"] == 0.0
