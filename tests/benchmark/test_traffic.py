"""The traffic generator and the client-side reduction: every seed
offers the same multisets, due times follow the gaps, and tokens are
counted by when they arrived."""

import collections
import json
import os
import statistics
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from benchmark.harness import traffic
from benchmark.harness.manifest import BENCH_DIR, load_json

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic")))
SERVING = [m for m in MIXES
           if load_json(BENCH_DIR, "traffic", m + ".json")["loop"] != "job"]


def mix(name):
    return load_json(BENCH_DIR, "traffic", name + ".json")


@pytest.mark.parametrize("name", SERVING)
def test_pairs_are_what_the_file_states(name):
    m = mix(name)
    pairs = traffic.length_pairs(m)
    said = m["stated"]
    assert len(pairs) == 64
    prompts = [p for p, _ in pairs]
    answers = [o for _, o in pairs]
    assert prompts == sorted(prompts)
    assert said["prompt_len"]["lo"] <= prompts[0]
    assert prompts[-1] <= said["prompt_len"]["hi"]
    assert said["output_len"]["lo"] <= min(answers)
    assert max(answers) <= said["output_len"]["hi"]
    # Stratified quantiles sit on the stated medians.
    assert abs(statistics.median(prompts) / said["prompt_len"]["median"]
               - 1) < 0.05
    assert abs(statistics.median(answers) / said["output_len"]["median"]
               - 1) < 0.05
    assert all(p + o <= said["max_total"] for p, o in pairs)
    assert traffic.longest_prompt(m) == prompts[-1]
    # Long prompts do not all get long answers.
    assert statistics.median(answers[:32]) == pytest.approx(
        statistics.median(answers[32:]), rel=0.1)


def test_gaps_are_what_the_file_states():
    m = mix("chat-open")
    gaps = traffic.arrival_gaps(m)
    assert len(gaps) == 64 and gaps == sorted(gaps) and gaps[0] > 0
    assert traffic.rate_rps(m) == pytest.approx(m["arrivals"]["rate_rps"],
                                                rel=1e-12)
    cv = statistics.pstdev(gaps) / statistics.mean(gaps)
    # Stratified quantiles cut the far tail, so a little under the cv
    # the file states.
    assert 0.9 * m["arrivals"]["cv"] < cv <= m["arrivals"]["cv"]
    # A gamma with shape 1 / cv^2 = 0.25: a quarter of the arrivals come
    # within a thousandth of the mean gap of the one before (bursts).
    mean = statistics.mean(gaps)
    assert 0.15 < sum(g < 0.01 * mean for g in gaps) / 64 < 0.35


@pytest.mark.parametrize("name", SERVING)
def test_every_seed_offers_the_same_multisets(name):
    m = mix(name)
    n = 3 * len(m["pairs"])
    streams = [traffic.request_stream(m, seed, n)
               for seed in (0, 7, 2 ** 31 + 5)]

    def lengths(s):
        return collections.Counter(
            (r["prompt_len"], r["max_tokens"]) for r in s["requests"])

    assert lengths(streams[0]) == lengths(streams[1]) == lengths(streams[2])
    # ... in another order, block by block the whole multiset.
    assert [r["prompt_len"] for r in streams[0]["requests"]] != \
        [r["prompt_len"] for r in streams[1]["requests"]]
    block = streams[1]["requests"][:len(m["pairs"])]
    assert collections.Counter(
        (r["prompt_len"], r["max_tokens"]) for r in block) == \
        collections.Counter(traffic.length_pairs(m))
    # The same seed gives the same stream.
    assert streams[2] == traffic.request_stream(m, 2 ** 31 + 5, n)


def test_open_loop_due_times_follow_the_gaps():
    m = mix("chat-open")
    n = 2 * len(m["gaps_s"])
    a, b = (traffic.request_stream(m, s, n)["requests"] for s in (1, 2))
    for reqs in (a, b):
        due = [r["due_s"] for r in reqs]
        assert due == sorted(due) and due[0] > 0
        # Whole blocks of the gap multiset: the same total for any seed,
        # at exactly the rate the file fixes.
        assert due[-1] == pytest.approx(n / m["arrivals"]["rate_rps"])
    gaps = [y - x for x, y in zip([0] + [r["due_s"] for r in a],
                                  [r["due_s"] for r in a])]
    assert sorted(gaps[:len(m["gaps_s"])]) == pytest.approx(
        traffic.arrival_gaps(m))
    assert m["arrivals"]["rate_rps"] == pytest.approx(
        0.8 * m["arrivals"]["knee_rps"])


def test_tokens_are_counted_by_arrival_time():
    records = [
        # across the opening edge: two of its four tokens are inside
        {"due": 8.0, "arrivals": [9.0, 9.5, 10.0, 10.5], "t_end": 10.5},
        # wholly inside
        {"due": 11.0, "arrivals": [11.5, 12.0, 12.25], "t_end": 12.3},
        # across the closing edge: one token inside, the edge itself out
        {"due": 19.0, "arrivals": [19.5, 20.0, 20.5], "t_end": 20.5},
        # due in the window, never answered
        {"due": 15.0, "arrivals": [], "t_end": 21.0},
    ]
    assert traffic.tokens_in_window(records, 10.0, 20.0) == 2 + 3 + 1
    assert sorted(traffic.gaps_in_window(records, 10.0, 20.0)) == \
        [0.25, 0.5, 0.5]
    assert sorted(traffic.first_token_waits(records, 10.0, 20.0)) == \
        [0.5, 0.5, 6.0]


def test_quantile_interpolates_like_numpy():
    assert traffic.quantile([], 0.5) is None
    assert traffic.quantile([3.0], 0.95) == 3.0
    assert traffic.quantile([1, 2, 3, 4], 0.5) == 2.5
    assert traffic.quantile(list(range(101)), 0.95) == 95


class _SSE(BaseHTTPRequestHandler):
    """Answers like the proxy: chunked `data: {...}\\n\\n` events, one
    token every few milliseconds, then `[DONE]`."""

    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.server.seen.append(body)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(data):
            self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
            self.wfile.flush()

        for i in range(body["max_tokens"]):
            time.sleep(0.004)
            chunk(b"data: " + json.dumps(
                {"token": body["prompt_ids"][0], "index": i}).encode()
                + b"\n\n")
        chunk(b"data: [DONE]\n\n")
        self.wfile.write(b"0\r\n\r\n")
        self.close_connection = True

    def log_message(self, *args):
        pass


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_load_generator_against_a_streaming_server(loop, tmp_path):
    server = ThreadingHTTPServer(("127.0.0.1", 0), _SSE)
    server.seen = []
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    plan = {"loop": loop, "seed": 3, "vocab": 100, "clients": 3,
            "host": "127.0.0.1", "port": server.server_port, "route": "/llm",
            "requests": [{"prompt_len": 5 + i % 3, "max_tokens": 4 + i % 2,
                          "due_s": 0.05 * (i + 1)} for i in range(400)]}
    (tmp_path / "plan.json").write_text(json.dumps(plan))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"),
         str(tmp_path / "plan.json"), str(tmp_path / "out.json")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        started = json.loads(proc.stdout.readline())["started"]
        # One machine, one monotonic clock: the child's is the parent's.
        assert abs(started - time.perf_counter()) < 5.0
        time.sleep(1.0)
        proc.stdin.write("stop 5\n")
        proc.stdin.flush()
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        server.shutdown()
        thread.join(timeout=10)
        server.server_close()
    out = json.loads((tmp_path / "out.json").read_text())
    assert len(out["records"]) == out["offered"] and not out["exhausted"]
    # After `stop` it waited only until each request in flight had its
    # first token; those were then cut off.
    records = [r for r in out["records"] if r["done"]]
    cut = [r for r in out["records"] if not r["done"]]
    assert len(records) >= 6 and len(cut) <= 3
    for r in cut:
        assert r["error"] is None and 1 <= len(r["arrivals"]) < r["max_tokens"]
    for r in records:
        assert r["status"] == 200 and r["error"] is None
        assert len(r["tokens"]) == len(r["arrivals"]) == r["max_tokens"]
        assert r["arrivals"] == sorted(r["arrivals"])
        assert r["due"] <= r["sent"] + 1e-9 <= r["arrivals"][0]
        # The prompt was drawn from (seed, index): the server echoes its
        # first id as every token.
        assert len(set(r["tokens"])) == 1 and 0 <= r["tokens"][0] < 100
    assert len({r["tokens"][0] for r in records}) > 3
    if loop == "open":
        for r in records:
            assert r["due"] == pytest.approx(
                out["t_start"] + plan["requests"][r["index"]]["due_s"])
        assert max(r["sent"] - r["due"] for r in records) < 0.25
    else:
        # Three clients, each sending its next request when one ends.
        ends = sorted(r["t_end"] for r in records)
        starts = sorted(r["sent"] for r in out["records"])
        assert all(s >= e - 1e-9 for s, e in zip(starts[3:], ends))
