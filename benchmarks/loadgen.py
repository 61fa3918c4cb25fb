"""The open-loop load generator: a child process that never imports JAX.

``python loadgen.py <url> <schedule.json> <out.json>`` reads the schedule
(traffic.make_schedule), waits for one line ``<t_start>\n`` on standard
input (seconds on time.time()'s clock), then sends each request when it
is due, whether or not earlier ones have been answered, from SENDERS
threads over the program's own client.  Each request is timed from when
it was due.  It writes one JSON list to <out.json> and exits.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

#: sender threads: each holds one request at a time, so an ACK that waits
#: a second or two behind an apply blocks one of many (with four, all four
#: blocked and the loop ran late; PERF.md section 4)
SENDERS = 32


def send_all(url: str, schedule, t_start: float, timeout_s: float = 120.0):
    from cook_tpu.client import JobClient

    lock = threading.Lock()
    cursor = [0]
    results = [None] * len(schedule)

    def sender() -> None:
        client = JobClient(url, user="nobody", timeout_s=timeout_s)
        client.throttle_retries = 0
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= len(schedule):
                break
            req = schedule[i]
            due = t_start + req["due"]
            wait = due - time.time()
            if wait > 0:
                time.sleep(wait)
            client.user = req["user"]
            specs = [{"uuid": j["uuid"], "command": "true", "name": "open",
                      "cpus": j["cpus"], "mem": j["mem"],
                      "priority": j["priority"], "max_retries": 1}
                     for j in req["jobs"]]
            sent = time.time()
            ok, error, acked = True, None, []
            try:
                acked = client.submit(specs, pool=req["pool"],
                                      indeterminate_retries=0)
            except Exception as exc:   # recorded, counted as failed
                ok, error = False, f"{type(exc).__name__}: {exc}"[:200]
            results[i] = {"i": i, "due": due, "sent": sent,
                          "ack": time.time(), "ok": ok, "error": error,
                          "acked": list(acked)}
        client.close()

    threads = [threading.Thread(target=sender, name=f"sender-{k}")
               for k in range(SENDERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def main(argv) -> int:
    url, schedule_path, out_path = argv[1:4]
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(schedule_path, encoding="utf-8") as f:
        schedule = json.load(f)
    print("ready", flush=True)
    line = sys.stdin.readline().strip()
    if not line:
        return 2        # the parent went away before the window
    results = send_all(url, schedule, float(line))
    assert "jax" not in sys.modules, "the load generator must not load JAX"
    with open(out_path + ".tmp", "w", encoding="utf-8") as f:
        json.dump(results, f)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
