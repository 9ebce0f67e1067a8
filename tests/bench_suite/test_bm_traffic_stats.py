"""The benchmark's own arithmetic: the stratified seeded generator and the
reduction from request timelines to end-to-end metrics.  No TPU library."""

import statistics
from collections import Counter

import pytest

from benchmarks import harness, loadgen, stats
from benchmarks.traffic_kinds import backlog, open_loop

CHAT = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
DECODE = harness.load_json(harness.HERE, "traffic", "batch-decode.json")
PREFILL = harness.load_json(harness.HERE, "traffic", "batch-prefill.json")
BIG = 3_000_000_019         # the driver's seeds do not fit 32 signed bits


def other(mix):
    """The same mix in another arrival order: another mix file would say so."""
    return dict(mix, layout_seed=mix["layout_seed"] + 1)


def _lens(plan, section, key):
    return Counter(item[key] for _, item in plan if item["section"] == section)


@pytest.mark.parametrize("section", ["lead_in", "window", "lead_out"])
def test_two_layouts_same_multiset_other_order(section):
    a, *_ = open_loop.schedule(CHAT, 48, 1)
    b, *_ = open_loop.schedule(other(CHAT), 48, 1)
    for key in ("prompt_len", "max_tokens", "greedy"):
        assert _lens(a, section, key) == _lens(b, section, key)
    order = lambda p: [i["prompt_len"] for _, i in p if i["section"] == section]
    assert order(a) != order(b)
    first = {"lead_in": 0.0, "window": 15.0, "lead_out": 63.0}[section]

    def gaps(p):
        ts = [first] + [t for t, i in p if i["section"] == section]
        return sorted(t1 - t0 for t0, t1 in zip(ts, ts[1:]))

    assert gaps(a) == pytest.approx(gaps(b), abs=1e-6)


def test_every_seed_replays_the_mixs_schedule_with_tokens_of_its_own():
    a, *_ = open_loop.schedule(CHAT, 48, 1)
    b, *_ = open_loop.schedule(CHAT, 48, BIG)
    shape = lambda p: [(round(t, 9), i["prompt_len"], i["max_tokens"],
                        i["greedy"]) for t, i in p]
    assert shape(a) == shape(b)
    assert [i["ids_seed"] for _, i in a] != [i["ids_seed"] for _, i in b]
    assert loadgen.body_of(a[0][1], CHAT, 32768) != \
        loadgen.body_of(b[0][1], CHAT, 32768)


@pytest.mark.parametrize("mix", [CHAT, DECODE, PREFILL],
                         ids=["chat", "decode", "prefill"])
def test_a_mix_without_its_schedule_is_an_error(mix):
    kind = open_loop.schedule if mix["kind"] == "open_loop" else None
    for key in ("layout_seed",) + (("cycle",) if kind is None else ()):
        cut = {k: v for k, v in mix.items() if k != key}
        with pytest.raises(KeyError):
            kind(cut, 48, 1) if kind else backlog.sequence(cut, 1)


def test_window_holds_rate_times_seconds_and_opens_after_lead_in():
    plan, t_open, t_close = open_loop.schedule(CHAT, 48, 5)
    win = [t for t, i in plan if i["section"] == "window"]
    assert len(win) == round(CHAT["rate_rps"] * 48)
    assert t_open == CHAT["lead_in_s"] and t_close == t_open + 48
    assert t_open < min(win) and max(win) == pytest.approx(t_close)
    lead = [t for t, i in plan if i["section"] == "lead_in"]
    assert lead and max(lead) <= t_open + 1e-9
    assert [t for t, _ in plan] == sorted(t for t, _ in plan)


def test_lengths_keep_tails_and_clips():
    plan, *_ = open_loop.schedule(CHAT, 48, 9)
    p = sorted(i["prompt_len"] for _, i in plan if i["section"] == "window")
    assert p[0] >= 32 and p[-1] <= 3072 and p[-1] > 2000
    assert 330 < statistics.median(p) < 440
    greedy = [i["greedy"] for _, i in plan if i["section"] == "window"]
    assert sum(greedy) == -(-len(greedy) // 4)


def test_same_seed_same_bodies():
    a, *_ = open_loop.schedule(CHAT, 10, BIG)
    b, *_ = open_loop.schedule(CHAT, 10, BIG)
    assert [loadgen.body_of(i, CHAT, 32768) for _, i in a[:5]] == \
           [loadgen.body_of(i, CHAT, 32768) for _, i in b[:5]]
    x, y = (loadgen.body_of(i, CHAT, 32768) for _, i in a[:2])
    assert x != y


@pytest.mark.parametrize("mix", [DECODE, PREFILL], ids=["decode", "prefill"])
def test_backlog_same_multiset_and_priming_keeps_totals(mix):
    a, b = backlog.sequence(mix, 3), backlog.sequence(other(mix), 3)
    # priming moves tokens from output to prompt and pairing is the layout's:
    # the backlog's total work is what every order shares
    total = lambda s: sum(i["prompt_len"] + i["max_tokens"] for i in s)
    assert total(a) == total(b)
    if not mix["prime_first_wave"]:
        for key in ("prompt_len", "max_tokens"):
            assert Counter(i[key] for i in a) == Counter(i[key] for i in b)
    assert [i["prompt_len"] for i in a] != [i["prompt_len"] for i in b]
    # and a seed changes the tokens of that one order, nothing else
    c = backlog.sequence(mix, BIG)
    assert [(i["prompt_len"], i["max_tokens"]) for i in a] == \
        [(i["prompt_len"], i["max_tokens"]) for i in c]
    assert [i["ids_seed"] for i in a] != [i["ids_seed"] for i in c]
    lim = harness.traffic_limits(mix)
    for i in a:
        assert 1 <= i["max_tokens"]
        assert lim["min_prompt"] <= i["prompt_len"] <= lim["max_prompt"]
        assert i["prompt_len"] + i["max_tokens"] <= lim["max_total"]
    primed = [i for i in a if i["section"] == "lead_in"]
    assert len(primed) == (mix["in_flight"] if mix["prime_first_wave"] else 0)


def test_stratified_is_the_quantile_midpoints():
    d = {"dist": "exponential", "mean": 2.0}
    xs = loadgen.stratified(d, 4)
    assert xs == pytest.approx([-2.0 * __import__("math").log(1 - p)
                                for p in (0.125, 0.375, 0.625, 0.875)])
    assert sum(loadgen.stratified_gaps(d, 50, 10.0)) == pytest.approx(10.0)
    with pytest.raises(ValueError):
        loadgen.quantile({"dist": "zipf"}, 0.5)


def _t(due, sent, chunks, section="window", ok=True, prompt_len=10):
    return {"section": section, "due": due, "sent": sent, "chunks": chunks,
            "end": chunks[-1][0] if chunks else None, "ok": ok,
            "prompt_len": prompt_len, "max_tokens": sum(c[1] for c in chunks)}


def test_ttft_is_timed_from_due_not_sent():
    t = _t(10.0, 10.2, [(10.5, 1), (10.6, 1)])
    assert stats.ttft_ms(t) == pytest.approx(500.0)


def test_tpot_and_gaps_on_a_hand_timeline():
    t = _t(0, 0, [(1.0, 1), (1.1, 1), (1.4, 2), (1.5, 1)])
    assert stats.tpot_ms(t) == pytest.approx(500.0 / 4)
    assert stats.token_gaps_ms(t) == pytest.approx([100, 300, 0, 100])
    assert stats.tpot_ms(_t(0, 0, [(1.0, 1)])) is None


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (100, 4.0), (99, 3.97)])
def test_percentile(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
    assert stats.percentile([], q) is None


def test_tokens_in_window_counts_arrivals_not_requests():
    tl = [_t(0, 0, [(0.9, 1), (1.5, 2), (2.5, 1)], section="lead_in"),
          _t(1, 1, [(1.2, 1), (1.9, 1)], prompt_len=100),
          _t(1, 1, [(2.0, 5)], prompt_len=7)]
    # window [1, 2): first request's first token came before it opened, so
    # its prompt does not count; the third's only event is at the close
    assert stats.tokens_in_window(tl, 1.0, 2.0) == (100, 4)


def test_counted_leaves_out_failures_and_other_sections():
    tl = [_t(0, 0, [(1, 1)]), _t(0, 0, [(1, 1)], ok=False),
          _t(0, 0, [(1, 1)], section="lead_out")]
    assert len(stats.counted(tl)) == 1


def test_iqr_share_is_the_drivers_spread():
    v = [10.0, 10.2, 10.4, 10.1, 9.9, 10.3]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert stats.iqr_share(v) == pytest.approx((q3 - q1) / statistics.median(v))


def test_sender_times_and_validates_against_a_fake_server():
    import http.server
    import json
    import threading

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()
            ev = lambda toks, fin: self.wfile.write(b"data: " + json.dumps(
                {"choices": [{"token_ids": toks, "finish_reason": fin}]}
            ).encode() + b"\n\n")
            ev([], None)
            for _ in range(n["max_tokens"] - n.get("short", 0)):
                ev([5], None)
            ev([], "length")
            self.wfile.write(b"data: [DONE]\n\n")

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        s = loadgen.Sender(srv.server_address[1], vocab=10)
        item = {"section": "window", "prompt_len": 3, "max_tokens": 4}
        good = s.send(item, b'{"max_tokens": 4}', due=s.clock() - 0.25)
        bad = s.send(item, b'{"max_tokens": 4, "short": 1}', due=s.clock())
    finally:
        srv.shutdown()
    assert good["ok"] and len(good["chunks"]) == 4
    assert stats.ttft_ms(good) >= 250.0      # lateness is inside the TTFT
    assert not bad["ok"] and "3 tokens" in bad["error"]
