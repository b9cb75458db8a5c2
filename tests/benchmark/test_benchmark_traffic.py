"""The generators: the same seed gives the same inputs, every seed the same
amount of work, and the open loop times from the due time."""
import numpy as np
import pytest

from benchmark import harness
from benchmark.kinds import open_loop as ol, train_job

BIG = 2 ** 31 + 11


@pytest.mark.parametrize("name", ["chat", "flood"])
def test_schedule_repeats_and_offers_the_same_work(name):
    mix = harness.load_json(harness.HERE, "traffic", name + ".json")
    seconds = mix["cycle_sessions"] / mix["sessions_per_s"]   # one cycle
    a = ol.schedule(mix, BIG, seconds, 32768)
    b = ol.schedule(mix, BIG, seconds, 32768)
    c = ol.schedule(mix, 12345, seconds, 32768)
    assert [(x.due_s, x.tokens) for x in a] == [(x.due_s, x.tokens) for x in b]
    assert [x.tokens for x in a] != [x.tokens for x in c]
    sessions = lambda s: sorted({(x.session, x.prefix_len) for x in s})  # noqa: E731
    assert abs(len(sessions(a)) - len(sessions(c))) <= 1
    total = lambda s: sum(len(x.tokens) + x.answer_len for x in s)      # noqa: E731
    assert abs(total(a) - total(c)) <= 0.15 * total(a)
    assert all(1 <= t < 32768 for x in a for t in x.tokens)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)


def test_chat_shapes():
    mix = harness.load_json(harness.HERE, "traffic", "chat.json")
    _, sessions = ol.cycle(mix)
    lens = [s["prefix_len"] + a["suffix_len"] for s in sessions
            for a in s["asks"]]
    assert 32 <= min(lens) and max(lens) <= 2048 + 32
    assert 330 <= np.median(lens) <= 440
    share = sum(1 for s in sessions if s["prefix_len"]) / len(sessions)
    assert share == pytest.approx(0.3, abs=0.03)
    answers = [a["answer_len"] for s in sessions for a in s["asks"]]
    assert 8 <= min(answers) and max(answers) <= 256


def test_flood_sessions_share_their_document():
    mix = harness.load_json(harness.HERE, "traffic", "flood.json")
    asks = ol.schedule(mix, 3, 60.0, 32768)
    by = {}
    for a in asks:
        by.setdefault(a.session, []).append(a)
    full = [v for v in by.values() if len(v) == 3]
    assert full
    for v in full:
        n = v[0].prefix_len
        assert 1024 <= n <= 7168
        assert v[0].tokens[:n] == v[1].tokens[:n] == v[2].tokens[:n]
        assert v[0].tokens[n:] != v[1].tokens[n:]
        assert all(2.0 <= b.due_s - a.due_s <= 6.0 for a, b in zip(v, v[1:]))


def test_quantile_grid_mean_and_clip():
    g = ol.quantile_grid({"dist": "exponential", "mean": 0.5}, 40)
    assert g.mean() == pytest.approx(0.5)
    g = ol.quantile_grid({"dist": "lognormal", "median": 64, "sigma": 0.7,
                          "min": 8, "max": 256}, 400)
    assert g.min() >= 8 and g.max() <= 256
    assert np.median(g) == pytest.approx(64, rel=0.02)


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class _SlowEngine:
    """Takes 2 s inside ``submit`` for the first request: the later ones
    are sent late, and their latency still counts from their due time."""

    def __init__(self, clock):
        self.clock, self.calls = clock, []

    def submit(self, tokens, max_new_tokens, temperature, on_token):
        if not self.calls:
            self.clock.sleep(2.0)

        class Req:
            slot_time = self.clock()
            arrival_time = self.clock()
            cached_prompt_tokens = 0
        self.clock.sleep(0.1)
        for tok in range(max_new_tokens):
            on_token(Req, tok)
            self.clock.sleep(0.05)
        self.calls.append(len(tokens))

        class Handle:
            def wait(self, timeout=None):
                return True
        return Handle()


def test_open_loop_times_from_the_due_time():
    clock = _Clock()
    asks = [ol.Ask(0.5, 0, -1, 0, 4, 2, [1, 2, 3, 4]),
            ol.Ask(1.0, 1, -2, 0, 4, 2, [5, 6, 7, 8])]
    records, t0 = ol.offer(_SlowEngine(clock), asks, 5.0, clock=clock,
                           sleep=clock.sleep)
    assert t0 == 100.0 and clock() >= 105.0
    first, second = records
    assert first.sent_s == pytest.approx(0.5)
    # the first submit returned at 0.5 + 2.0 + 0.1 + 2 * 0.05 = 2.7
    assert second.sent_s == pytest.approx(2.7)
    e2e = ol.end_to_end(records, 5.0, horizon_s=5.0)
    ttft_second = second.token_s[0] - 1.0          # from due, not from sent
    assert ttft_second == pytest.approx(1.8)
    assert first.token_s[0] - 0.5 == pytest.approx(2.1)
    assert e2e["ttft_p95_ms"] == pytest.approx(1e3 * (1.8 + 0.95 * 0.3))
    # prompt tokens count at the first token, generated ones as they come
    assert e2e["served_tokens"] == 2 * (4 + 2)
    assert e2e["itl_p95_ms"] == pytest.approx(50.0)


def test_unfinished_request_misses_any_limit():
    rec = ol.Record(ol.Ask(1.0, 0, -1, 0, 4, 2, [1, 2, 3, 4]))
    e2e = ol.end_to_end([rec], 5.0, horizon_s=30.0)
    assert e2e["ttft_p95_ms"] == pytest.approx(29e3)
    assert e2e["served_tokens"] == 0


def test_step_rows_from_the_request_log():
    rec = ol.Record(ol.Ask(0.0, 0, -1, 0, 300, 3, list(range(1, 301))))
    rec.slot_s, rec.token_s, rec.tokens = 1.0, [4.0, 4.2, 4.4], [7, 8, 9]
    rec.cached_prompt_tokens = 128
    rows = ol.step_rows([rec], 0.0, 10.0, prefill_chunk=112)
    # 172 uncached tokens in two chunks (112 on 128, 60 on 240), two decodes
    assert sorted(rows) == sorted([(112, 128), (60, 240), (1, 300), (1, 301)])
    assert ol.step_rows([rec], 4.1, 4.3, 112) == [(1, 300)]


def test_train_batches_repeat_and_rows_differ():
    mix = {"batch": 2, "seq_len": 32}
    a = train_job.batches(mix, BIG, 256, 3)
    b = train_job.batches(mix, BIG, 256, 3)
    assert all((x == y).all() for x, y in zip(a, b))
    rows = [tuple(r) for x in a for r in x]
    assert len(set(rows)) == len(rows)


def test_train_loop_keeps_steps_in_flight_and_closes_on_the_last():
    clock, order = _Clock(), []

    class Loss:
        def __init__(self, i):
            self.i = i

        def numpy(self):
            order.append(("wait", self.i))
            clock.sleep(0.3)
            return 1.0

    def step(feed):
        order.append(("call", feed))
        return Loss(feed)

    done, losses, window = train_job.loop(step, [0, 1, 2], 1.0, in_flight=2,
                                          clock=clock)
    assert order[:4] == [("call", 0), ("call", 1), ("call", 2), ("wait", 0)]
    assert len(done) == len(losses) == sum(1 for o in order if o[0] == "call")
    assert window == pytest.approx(done[-1])
