"""Loopback tests for the HTTP/SSE serving frontend (ISSUE 3).

A real :class:`CompletionServer` runs on an asyncio loop in a background
thread; tests speak actual HTTP over ``http.client`` on 127.0.0.1 —
concurrent SSE streams, admission-control 429s, request deadlines,
graceful drain, and the Prometheus ``/metrics`` page.  Everything runs
on the toy Llama under ``JAX_PLATFORMS=cpu`` (tier-1)."""

import asyncio
import http.client
import json
import os
import re
import subprocess
import sys
import threading
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import LLM, EngineCore, SamplingParams, SchedulerConfig
from paddle_tpu.serving.protocol import (
    ProtocolError,
    parse_completion_request,
    sse_event,
)
from paddle_tpu.serving.server import CompletionServer, ServerConfig

PROMPTS = [[5, 9, 23, 7], [40, 2, 11], [1, 2, 3, 4, 5, 6], [100, 101]]
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(layers=2):
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))


def _engine(model, num_blocks=64, block_size=4, max_num_seqs=4):
    return EngineCore(model, num_blocks=num_blocks, block_size=block_size,
                      scheduler_config=SchedulerConfig(
                          max_num_seqs=max_num_seqs))


class Harness:
    """A live CompletionServer on an asyncio loop in a daemon thread."""

    def __init__(self, engine, cfg=None):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.server = CompletionServer(engine, cfg or ServerConfig())
        self.run(self.server.start())
        self.port = self.server.port

    def run(self, coro, timeout=120):
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def submit(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop)

    def close(self):
        try:
            self.run(self.server.shutdown(drain_timeout=1.0), timeout=60)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(10)
            self.loop.close()


@pytest.fixture
def harness_factory():
    live = []

    def make(engine, cfg=None):
        h = Harness(engine, cfg)
        live.append(h)
        return h

    yield make
    for h in live:
        h.close()


# --- raw HTTP helpers -------------------------------------------------------

def _request(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = None if body is None else json.dumps(body)
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = resp.read()
    headers = {k.lower(): v for k, v in resp.getheaders()}
    conn.close()
    return resp.status, headers, data


def _sse_request(port, body, timeout=120, stop_after=None, frames=None):
    """POST a streaming completion; parse SSE frames.  Returns
    (tokens, finish_reason, saw_done).  ``stop_after=n`` closes the
    connection after n tokens (client walks away); ``frames`` (a list)
    collects every parsed chunk."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/completions", json.dumps(dict(body, stream=True)),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    assert resp.getheader("Content-Type") == "text/event-stream"
    tokens, finish, done = [], None, False
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.rstrip(b"\n")
        if not line:
            continue  # blank separator between events
        assert line.startswith(b"data: "), line
        payload = line[len(b"data: "):]
        if payload == b"[DONE]":
            done = True
            break
        obj = json.loads(payload)
        if frames is not None:
            frames.append(obj)
        choice = obj["choices"][0]
        tokens.extend(choice["token_ids"])
        if choice["finish_reason"] is not None:
            finish = choice["finish_reason"]
        if stop_after is not None and len(tokens) >= stop_after:
            break
    conn.close()
    return tokens, finish, done


# --- protocol unit tests ----------------------------------------------------

class TestProtocol:
    def test_parse_minimal_and_defaults(self):
        req = parse_completion_request(b'{"prompt": [1, 2, 3]}')
        assert req.prompt_ids == [1, 2, 3]
        assert req.max_tokens == 16 and not req.stream
        assert req.sampling().temperature == 0.0

    @pytest.mark.parametrize("body", [
        b"not json",
        b'[1,2]',
        b'{}',
        b'{"prompt": []}',
        b'{"prompt": ["a"]}',
        b'{"prompt": "hi"}',              # no tokenizer configured
        b'{"prompt": [1], "max_tokens": 0}',
        b'{"prompt": [1], "max_tokens": "4"}',
        b'{"prompt": [1], "temperature": -1}',
        b'{"prompt": [1], "temperature": NaN}',   # json accepts the literal
        b'{"prompt": [1], "temperature": Infinity}',
        b'{"prompt": [1], "timeout": 0}',
        b'{"prompt": [1], "timeout": NaN}',
        b'{"prompt": [1], "seed": -1}',           # np rng wants seed >= 0
        b'{"prompt": [1], "stream": 1}',
    ])
    def test_parse_rejects(self, body):
        with pytest.raises(ProtocolError):
            parse_completion_request(body)

    def test_string_prompt_with_tokenizer(self):
        req = parse_completion_request(
            b'{"prompt": "abc"}', tokenize=lambda s: [ord(c) for c in s])
        assert req.prompt_ids == [97, 98, 99]

    def test_sse_event_framing(self):
        ev = sse_event({"a": 1})
        assert ev == b'data: {"a":1}\n\n'


# --- loopback integration ---------------------------------------------------

class TestEndpoints:
    def test_health_ready_metrics_and_404(self, harness_factory):
        h = harness_factory(_engine(_model()))
        assert _request(h.port, "GET", "/healthz")[0] == 200
        assert _request(h.port, "GET", "/readyz")[0] == 200
        status, headers, body = _request(h.port, "GET", "/metrics")
        assert status == 200
        assert headers["content-type"].startswith(
            "text/plain; version=0.0.4")
        assert _request(h.port, "GET", "/nope")[0] == 404
        assert _request(h.port, "GET", "/v1/completions")[0] == 405

    def test_bad_request_400(self, harness_factory):
        h = harness_factory(_engine(_model()))
        status, _, data = _request(h.port, "POST", "/v1/completions",
                                   {"max_tokens": 4})
        assert status == 400
        assert "prompt" in json.loads(data)["error"]["message"]

    def test_completion_roundtrip_token_identical(self, harness_factory):
        m = _model()
        ref = LLM(m, num_blocks=64, block_size=4).generate(
            [PROMPTS[0]], SamplingParams(max_new_tokens=6))[0]
        h = harness_factory(_engine(m))
        status, _, data = _request(h.port, "POST", "/v1/completions",
                                   {"prompt": PROMPTS[0], "max_tokens": 6})
        assert status == 200
        obj = json.loads(data)
        choice = obj["choices"][0]
        assert choice["token_ids"] == ref.token_ids
        assert choice["finish_reason"] == "length"
        assert obj["usage"] == {"prompt_tokens": 4, "completion_tokens": 6,
                                "total_tokens": 10,
                                "prompt_cached_tokens": 0}

    def test_concurrent_sse_streams_token_identical(self, harness_factory):
        """The acceptance criterion: ≥4 concurrent SSE streaming requests
        complete token-identical to offline LLM.generate under greedy
        sampling, with the jitted-step compile count still bounded by the
        shape buckets (in-trace counters)."""
        m = _model()
        refs = [o.token_ids for o in LLM(
            m, num_blocks=64, block_size=4, max_num_seqs=4).generate(
                PROMPTS, SamplingParams(max_new_tokens=6))]
        engine = _engine(m, max_num_seqs=4)
        h = harness_factory(engine)

        results = [None] * len(PROMPTS)

        def worker(i):
            results[i] = _sse_request(
                h.port, {"prompt": PROMPTS[i], "max_tokens": 6})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        for (tokens, finish, done), ref in zip(results, refs):
            assert tokens == ref
            assert finish == "length"
            assert done                       # [DONE] terminated the stream
        # fixed-shape discipline survives the HTTP layer
        assert engine.decode_trace_count <= len(engine.decode_buckets)
        assert engine.prefill_trace_count <= len(engine.prefill_buckets)

    def test_metrics_page_exposes_serving_series(self, harness_factory):
        h = harness_factory(_engine(_model()))
        _request(h.port, "POST", "/v1/completions",
                 {"prompt": PROMPTS[0], "max_tokens": 3})
        status, headers, data = _request(h.port, "GET", "/metrics")
        assert status == 200
        text = data.decode()
        assert ("# TYPE serving_time_to_first_token_seconds histogram"
                in text)
        assert "serving_time_to_first_token_seconds_bucket{le=" in text
        assert "serving_inter_token_latency_seconds_bucket{le=" in text
        assert "serving_admission_rejected_total 0" in text
        # the http counter ticks just after the response flushes; allow
        # the scrape a moment to observe it
        pat = (r'serving_http_requests_total\{code="200",'
               r'route="/v1/completions"\} 1')
        deadline = time.monotonic() + 5
        while not re.search(pat, text) and time.monotonic() < deadline:
            time.sleep(0.02)
            text = _request(h.port, "GET", "/metrics")[2].decode()
        assert re.search(pat, text)
        # every sample line is valid exposition: name{labels}? value
        sample = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
            r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$")
        for line in text.strip().splitlines():
            if not line.startswith("#"):
                assert sample.match(line), line


# --- the stream hand-off (ISSUE 32) -----------------------------------------

def _sse_full(port, body):
    """A streamed completion: (tokens, finish_reason, usage block of the
    final chunk, [DONE] seen).  Only the final chunk may end the
    request, and only it carries usage."""
    frames = []
    tokens, finish, done = _sse_request(port, body, frames=frames)
    assert all(f["choices"][0]["finish_reason"] is None
               and "usage" not in f for f in frames[:-1])
    return tokens, finish, frames[-1].get("usage"), done


def _in_threads(n, fn):
    out = [None] * n

    def worker(i):
        out[i] = fn(i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    return threads, out


def _stream_series(server):
    """(wakes, coalesced, handles woken) of a fleet made from a bare
    engine: unlabeled, like its other serving series."""
    c = server.registry.counter
    return (int(c("serving_stream_wakes_total").value),
            int(c("serving_stream_wakes_coalesced_total").value),
            int(c("serving_stream_handles_woken_total").value))


def _wait_for(cond, seconds=30.0):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


class TestStreamHandoff:
    """How a step's news reaches the handlers that stream it: one
    callback posted to the loop a step and a replica, the walk over the
    handles on the loop thread, a wake only for a handle with news."""

    def _held_server(self, harness_factory, hold_intake, max_num_seqs,
                     num_blocks=512):
        engine = EngineCore(
            _model(layers=1), num_blocks=num_blocks, block_size=4,
            prefix_cache=False,
            scheduler_config=SchedulerConfig(max_num_seqs=max_num_seqs))
        h = harness_factory(engine, ServerConfig(max_queue=64))
        replica = h.server.fleet.replicas[0]
        return h, replica, hold_intake(replica)

    @pytest.mark.parametrize("n", [1, 8, 64])
    def test_one_loop_callback_a_step(self, n, harness_factory,
                                      hold_intake):
        """With n streams open the engine thread makes exactly one
        ``call_soon_threadsafe`` a step (none where the last had not
        run yet), and every stream reads what a non-streamed run of the
        same requests reads."""
        h, replica, gate = self._held_server(harness_factory, hold_intake,
                                             max_num_seqs=64)
        prompts = [[(7 * i + j) % 250 + 1 for j in range(4)]
                   for i in range(n)]
        posted = []
        real = h.loop.call_soon_threadsafe

        def counting(callback, *args, **kw):
            if threading.current_thread() is replica.thread:
                posted.append(callback)
            return real(callback, *args, **kw)

        h.loop.call_soon_threadsafe = counting

        def run(fn):
            """All n requests open before the first step."""
            gate.clear()
            threads, out = _in_threads(n, fn)
            assert _wait_for(lambda: len(h.server._handles) == n)
            gate.set()
            for t in threads:
                t.join(120)
            return out

        streamed = run(lambda i: _sse_full(
            h.port, {"prompt": prompts[i], "max_tokens": 6}))
        # the last step's notify may still be on its way
        assert _wait_for(lambda: sum(_stream_series(h.server)[:2])
                         == replica.steps_done, 5.0)
        steps = replica.steps_done
        wakes, coalesced, woken = _stream_series(h.server)
        assert steps >= 6
        assert len(posted) == wakes          # one system call a wake ...
        assert wakes + coalesced == steps    # ... and at most one a step
        assert all(cb == h.server._wake_streams for cb in posted)
        # a handle is woken for a new token or its end, never for
        # nothing: at least once each, at most once a token and once
        # more where a walk fell between a last token and its finish
        assert n <= woken <= (6 + 1) * n

        plain = run(lambda i: json.loads(_request(
            h.port, "POST", "/v1/completions",
            {"prompt": prompts[i], "max_tokens": 6})[2]))
        for (tokens, finish, usage, done), ref in zip(streamed, plain):
            assert done
            assert tokens == ref["choices"][0]["token_ids"]
            assert len(tokens) == 6
            assert finish == ref["choices"][0]["finish_reason"] == "length"
            assert usage == ref["usage"]

    def test_handle_without_news_is_not_woken(self, harness_factory,
                                              hold_intake):
        """Eight streams open, two rows a step: the six requests still
        queued have no news and their handlers sleep, so the events set
        count the emitting rows and not the open handles."""
        h, replica, gate = self._held_server(harness_factory, hold_intake,
                                             max_num_seqs=2)
        n, max_tokens = 8, 8
        threads, out = _in_threads(n, lambda i: _sse_full(
            h.port, {"prompt": [10 + i, 20 + i, 30 + i],
                     "max_tokens": max_tokens}))
        assert _wait_for(lambda: len(h.server._handles) == n)
        gate.set()
        for t in threads:
            t.join(120)
        assert all(len(tokens) == max_tokens and finish == "length"
                   for tokens, finish, _, _ in out)
        assert _wait_for(lambda: sum(_stream_series(h.server)[:2])
                         == replica.steps_done, 5.0)
        wakes, coalesced, woken = _stream_series(h.server)
        # waking every open handle a step would have set about
        # (8 + 6 + 4 + 2) x 8 = 160 events
        assert replica.steps_done >= 4 * max_tokens
        assert n <= woken <= n * (max_tokens + 1)

    @pytest.mark.parametrize("ending", ["length", "eos", "abort",
                                        "deadline", "rejected"])
    def test_no_ending_waits_for_the_poll(self, ending, harness_factory,
                                          monkeypatch):
        """With the handlers' safety-net poll at 30 s every request
        still ends at once: its last news came with a step's wake-up."""
        from paddle_tpu.serving import server as server_mod
        from paddle_tpu.serving.request import FinishReason

        engine = _engine(_model(layers=1), num_blocks=32)
        h = harness_factory(engine)
        body = {"prompt": PROMPTS[0], "max_tokens": 6}
        first = json.loads(_request(h.port, "POST", "/v1/completions",
                                    body)[2])["choices"][0]["token_ids"]
        monkeypatch.setattr(server_mod, "_POLL_S", 30.0)
        want_tokens = None
        if ending == "length":
            want, want_tokens = "length", first
        elif ending == "eos":
            body["eos_token_id"] = first[2]
            want, want_tokens = "eos", first[:first.index(first[2]) + 1]
        elif ending == "deadline":
            body.update(max_tokens=10000, timeout=0.3)
            want = "timeout"
        elif ending == "rejected":
            # more blocks than the pool has: refused at admission,
            # inside the step that planned it
            body["prompt"] = list(range(1, 200))
            want, want_tokens = "abort", []
        else:
            body["max_tokens"] = 10000
            want = "abort"
        t0 = time.monotonic()
        threads, out = _in_threads(1, lambda i: _sse_full(h.port, body))
        if ending == "abort":
            assert _wait_for(lambda: any(
                hd.req is not None and hd.req.output_tokens
                for hd in list(h.server._handles.values())))
            (hd,) = h.server._handles.values()
            h.loop.call_soon_threadsafe(h.server._request_abort, hd,
                                        FinishReason.ABORT)
        threads[0].join(60)
        tokens, finish, usage, done = out[0]
        assert time.monotonic() - t0 < 10.0, "an ending waited for the poll"
        assert done and finish == want
        if want_tokens is not None:
            assert tokens == want_tokens
        assert usage["completion_tokens"] == len(tokens)

    def test_metrics_page_carries_the_three_series(self, harness_factory):
        h = harness_factory(_engine(_model(layers=1)))
        text = _request(h.port, "GET", "/metrics")[2].decode()
        for name in ("serving_stream_wakes_total",
                     "serving_stream_wakes_coalesced_total",
                     "serving_stream_handles_woken_total"):
            assert f"# TYPE {name} counter" in text
            assert f"\n{name} 0\n" in text   # there from the first scrape
        _sse_full(h.port, {"prompt": PROMPTS[1], "max_tokens": 4})
        wakes, coalesced, woken = _stream_series(h.server)
        assert wakes >= 1 and 1 <= woken <= 4


class TestKeepAlive:
    """HTTP/1.1 persistent connections (ISSUE 4 satellite; ISSUE 3
    follow-up (a)): sequential requests ride ONE socket instead of a
    connection per request."""

    def test_two_sequential_completions_over_one_socket(self,
                                                        harness_factory):
        h = harness_factory(_engine(_model()))
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=120)
        got = []
        for prompt in (PROMPTS[0], PROMPTS[1]):
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": prompt, "max_tokens": 4}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            assert resp.status == 200
            assert resp.getheader("Connection") == "keep-alive"
            got.append(json.loads(data)["choices"][0]["token_ids"])
            assert conn.sock is not None   # server left the socket open
            if len(got) == 1:
                local = conn.sock.getsockname()
        # same client socket served both completions (no reconnect)
        assert conn.sock.getsockname() == local
        assert all(len(t) == 4 for t in got)
        conn.close()

    def test_mixed_routes_share_one_socket(self, harness_factory):
        h = harness_factory(_engine(_model()))
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=120)
        conn.request("GET", "/healthz")
        r = conn.getresponse()
        assert r.status == 200 and r.read() == b"ok\n"
        local = conn.sock.getsockname()
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        assert r.status == 200 and b"serving_engine_steps_total" in r.read()
        assert conn.sock.getsockname() == local
        conn.close()

    def test_connection_close_header_honored(self, harness_factory):
        h = harness_factory(_engine(_model()))
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=120)
        conn.request("GET", "/healthz", headers={"Connection": "close"})
        r = conn.getresponse()
        assert r.status == 200
        assert r.getheader("Connection") == "close"
        r.read()
        # http.client tears the socket down when the server says close
        assert conn.sock is None
        conn.close()

    def test_chunked_transfer_encoding_rejected_and_closed(
            self, harness_factory):
        """A chunked body would desync the persistent stream (its unread
        bytes would parse as the next request line), so the server must
        answer 411 AND close rather than keep the socket alive."""
        h = harness_factory(_engine(_model()))
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=120)
        body = json.dumps({"prompt": PROMPTS[0], "max_tokens": 2})
        payload = (f"{len(body):x}\r\n{body}\r\n0\r\n\r\n").encode()
        conn.putrequest("POST", "/v1/completions",
                        skip_accept_encoding=True)
        conn.putheader("Transfer-Encoding", "chunked")
        conn.putheader("Content-Type", "application/json")
        conn.endheaders()
        conn.send(payload)
        r = conn.getresponse()
        assert r.status == 411
        assert r.getheader("Connection") == "close"
        r.read()
        assert conn.sock is None  # server closed; stray bytes discarded
        conn.close()

    def test_idle_connection_reaped_after_timeout(self, harness_factory):
        h = harness_factory(_engine(_model()),
                            ServerConfig(keepalive_timeout_s=0.3))
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=120)
        conn.request("GET", "/healthz")
        r = conn.getresponse()
        assert r.status == 200 and r.getheader("Connection") == "keep-alive"
        r.read()
        sock = conn.sock
        sock.settimeout(10)
        # past the idle deadline the SERVER closes: recv sees clean EOF
        assert sock.recv(1) == b""
        conn.close()


class TestAdmissionControl:
    def test_429_with_retry_after_when_saturated(self, harness_factory):
        """With max_queue=1 and one stream in flight, the next POST is
        rejected 429 with a Retry-After header and the
        serving_admission_rejected_total counter increments."""
        m = _model()
        engine = _engine(m, num_blocks=256)
        h = harness_factory(engine, ServerConfig(max_queue=1,
                                                 retry_after_s=7))
        got_token = threading.Event()
        first = {}

        def long_stream():
            conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                              timeout=120)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": PROMPTS[0],
                                     "max_tokens": 120, "stream": True}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            tokens, finish, done = [], None, False
            while True:
                line = resp.readline().rstrip(b"\n")
                if not line:
                    if not resp.isclosed():
                        continue
                    break
                payload = line[len(b"data: "):]
                if payload == b"[DONE]":
                    done = True
                    break
                choice = json.loads(payload)["choices"][0]
                tokens.extend(choice["token_ids"])
                if tokens:
                    # the stream provably holds the only admission slot
                    got_token.set()
                if choice["finish_reason"] is not None:
                    finish = choice["finish_reason"]
            conn.close()
            first["result"] = (tokens, finish, done)

        t = threading.Thread(target=long_stream)
        t.start()
        assert got_token.wait(60), "first stream never produced a token"
        status, headers, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": PROMPTS[1], "max_tokens": 2})
        assert status == 429
        assert headers["retry-after"] == "7"
        assert json.loads(data)["error"]["type"] == "overloaded_error"
        t.join(120)
        tokens, finish, done = first["result"]
        assert done and finish == "length" and len(tokens) == 120
        # the rejection was counted; the admitted stream was unaffected
        _, _, metrics = _request(h.port, "GET", "/metrics")
        assert b"serving_admission_rejected_total 1" in metrics
        assert engine.kv.num_available == engine.kv.num_blocks - 1


class TestDeadlines:
    def test_request_timeout_returns_partial(self, harness_factory):
        m = _model()
        engine = _engine(m, num_blocks=256)
        h = harness_factory(engine)
        t0 = time.monotonic()
        status, _, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": PROMPTS[0], "max_tokens": 10000, "timeout": 0.3})
        assert status == 200
        choice = json.loads(data)["choices"][0]
        assert choice["finish_reason"] == "timeout"
        assert len(choice["token_ids"]) < 10000    # partial output
        assert time.monotonic() - t0 < 60
        # abort propagated into the scheduler: blocks freed
        deadline = time.monotonic() + 30
        while (engine.kv.num_available != engine.kv.num_blocks - 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert engine.kv.num_available == engine.kv.num_blocks - 1
        _, _, metrics = _request(h.port, "GET", "/metrics")
        assert b"serving_requests_finished_timeout_total 1" in metrics


class TestDrain:
    def test_graceful_drain(self, harness_factory):
        """shutdown(): /readyz flips to 503 immediately, new requests get
        503, in-flight requests finish or hit the drain deadline, and no
        KV blocks leak (pool occupancy zero at exit)."""
        m = _model()
        engine = _engine(m, num_blocks=256)
        h = harness_factory(engine)
        assert _request(h.port, "GET", "/readyz")[0] == 200

        stream_out = {}

        def long_stream():
            stream_out["result"] = _sse_request(
                h.port, {"prompt": PROMPTS[0], "max_tokens": 5000})

        t = threading.Thread(target=long_stream)
        t.start()
        # wait for the stream to be admitted (in-flight) before draining
        deadline = time.monotonic() + 60
        while (not engine.metrics.counters["requests_admitted"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert engine.metrics.counters["requests_admitted"] == 1

        fut = h.submit(h.server.shutdown(drain_timeout=0.3))
        # readiness flips the moment the drain begins
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if _request(h.port, "GET", "/readyz")[0] == 503:
                break
            time.sleep(0.01)
        assert _request(h.port, "GET", "/readyz")[0] == 503
        # no new admission while draining
        status, _, data = _request(h.port, "POST", "/v1/completions",
                                   {"prompt": PROMPTS[1], "max_tokens": 2})
        assert status == 503
        assert json.loads(data)["error"]["type"] == "unavailable_error"

        fut.result(timeout=60)
        t.join(60)
        tokens, finish, done = stream_out["result"]
        assert done and finish == "timeout"        # drain-deadline abort
        # no KV blocks leaked: pool occupancy zero at exit
        assert engine.kv.occupancy() == 0.0
        assert engine.kv.num_available == engine.kv.num_blocks - 1
        assert not h.server._engine_thread.is_alive()
        # the socket is closed: connections now fail
        with pytest.raises(OSError):
            _request(h.port, "GET", "/healthz", timeout=2)


class TestEngineDeath:
    def test_dead_engine_thread_turns_away_requests(self, harness_factory):
        """If the engine thread dies (any step() exception), in-flight
        handlers finish instead of hanging and NEW requests get 503 —
        they must not be queued for a thread nobody runs."""
        engine = _engine(_model())
        h = harness_factory(engine)

        def boom():
            raise RuntimeError("induced engine crash")

        engine.step_ahead = boom
        # this request crashes the engine loop; its handler must still
        # answer (finish_reason abort, empty output), not hang
        status, _, data = _request(h.port, "POST", "/v1/completions",
                                   {"prompt": PROMPTS[0], "max_tokens": 4})
        assert status == 200
        choice = json.loads(data)["choices"][0]
        assert choice["finish_reason"] == "abort"
        assert choice["token_ids"] == []
        # engine thread is gone: readiness and admission both say 503
        deadline = time.monotonic() + 10
        while (h.server._engine_thread.is_alive()
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert not h.server._engine_thread.is_alive()
        assert "induced engine crash" in h.server._engine_error
        assert _request(h.port, "GET", "/readyz")[0] == 503
        status, _, data = _request(h.port, "POST", "/v1/completions",
                                   {"prompt": PROMPTS[1], "max_tokens": 2})
        assert status == 503
        assert json.loads(data)["error"]["message"] == "engine is not running"
        # but liveness and metrics still serve
        assert _request(h.port, "GET", "/healthz")[0] == 200
        assert _request(h.port, "GET", "/metrics")[0] == 200


class TestSelftest:
    def test_module_selftest_subprocess(self):
        """`python -m paddle_tpu.serving.server --selftest` boots on an
        ephemeral port, serves one completion, exits 0 (the CI hook)."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving.server",
             "--selftest"],
            cwd=_REPO, env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "selftest: OK" in proc.stdout
