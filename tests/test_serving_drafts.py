"""A serving step that verifies a draft a sequence and yields one or two
tokens (``ServingEngine(draft_tokens=1)``, docs/SERVING.md "Drafts and
verify rows"): the tokens served are those of the undrafted engine, token
for token, at any acceptance: at chance (a vocabulary of 8), with a drafter
planted always right and one always wrong, through chunked prefill,
preemption and resume, a prefix hit, EOS or ``max_new_tokens`` falling on
the first of two tokens, a window shorter than a page, under either reader
and under the run loop; ``on_token`` fires once a token in order, no page
leaks, the step compiles once; ``draft_tokens=0`` compiles the step of a
model without a drafter; a model without one is refused; and a rejected
draft's row left visible fails."""
import collections
import re

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                          ExaoneMoeForCausalLM)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import serving_metrics

ENGINE = dict(max_batch=4, max_blocks={"window": 24, "full": 48},
              block_size=8, prefill_chunk=16)


@pytest.fixture(scope="module", autouse=True)
def own_expert_rows():
    """``serving_moe_expert_rows_total`` is one family a process, and a test
    worker runs several files in one: this file's engines must neither read
    another model's rows nor leave theirs behind (see test_smallthinker.py)."""
    from paddle_tpu.serving.engine import serving_metrics
    family = serving_metrics()["moe_rows"]
    family.clear()
    yield
    family.clear()


def _model(plant=None, **kw):
    pt.seed(0)
    model = ExaoneMoeForCausalLM(ExaoneMoeConfig.tiny(vocab_size=8, **kw))
    model.eval()
    if plant is not None:
        # every block adds nothing to the stream: the model's next token is
        # a function of its newest token alone, and a drafter that reads
        # the next token's embedding alone is right; its negation is wrong
        d = model.cfg.hidden_size
        for name, p in model.named_parameters():
            if re.search(r"(o_proj|down_proj)\.weight$|w_down$", name):
                p.set_value(np.zeros(p.shape, np.float32))
        sign = 1.0 if plant == "right" else -1.0
        model.mtp.eh_proj.weight.set_value(np.concatenate(
            [sign * np.eye(d, dtype=np.float32),
             np.zeros((d, d), np.float32)]))
    return model


@pytest.fixture(scope="module")
def chance():
    return _model()


def _prompts(seed=0, lengths=(5, 23, 40, 9)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 8, n).tolist() for n in lengths]


def _serve(model, drafts, prompts, new=20, loop=False, engine=None, **kw):
    """Tokens, the order ``on_token`` saw them in, and the engine."""
    submit = {k: kw.pop(k) for k in ("eos_token_id", "temperature")
              if k in kw}
    eng = engine or ServingEngine(model, draft_tokens=drafts,
                                  **dict(ENGINE, **kw))
    seen = collections.defaultdict(list)
    if loop:
        eng.start()
    handles = [eng.submit(p, max_new_tokens=new,
                          on_token=lambda r, t: seen[r.req_id].append(t),
                          **submit) for p in prompts]
    if loop:
        for h in handles:
            assert h.wait(300)
        eng.shutdown()
    else:
        eng.run_until_idle()
    eng.cache.assert_no_leaks()
    assert eng.step_traces == 1
    for h in handles:
        assert seen[h.req_id] == h.token_ids       # once a token, in order
    return [h.token_ids for h in handles], eng


@pytest.mark.parametrize("impl", ["gather", "rpa"])
def test_drafted_tokens_are_the_undrafted_tokens_at_chance(chance, impl):
    want, _ = _serve(chance, 0, _prompts(), attn_impl=impl)
    got, eng = _serve(chance, 1, _prompts(), attn_impl=impl)
    assert got == want
    d = eng.stats()["drafts"]
    assert 0 < d["accepted"] < d["drafted"]        # both branches ran
    assert d["emitted"] == 4 * 19 == d["decode_seqs"] + d["accepted"]
    assert 1.0 < d["emitted"] / d["decode_seqs"] < 2.0


@pytest.mark.parametrize("plant", ["right", "wrong"])
def test_a_planted_drafter_changes_the_count_and_not_the_tokens(plant):
    model = _model(plant)
    want, _ = _serve(model, 0, _prompts(1))
    got, eng = _serve(model, 1, _prompts(1))
    assert got == want
    d = eng.stats()["drafts"]
    assert d["drafted"] > 0
    if plant == "right":
        # 19 tokens after the first: nine steps of two and one of one
        assert d["accepted"] == d["drafted"] == 4 * 9
        assert d["decode_seqs"] == 4 * 10
    else:
        assert d["accepted"] == 0 and d["decode_seqs"] == 4 * 19
        assert d["drafted"] == 4 * 18              # the last wants one token


def test_through_preemption_and_resume(chance):
    """Three sequences of 5 pages each over a full pool of 10: the
    youngest is preempted, its drafts dropped, and recomputes."""
    prompts = _prompts(2, (20, 21, 22))
    tight = {"max_blocks": {"window": 16, "full": 10}}
    want, _ = _serve(chance, 0, prompts, **tight)
    got, eng = _serve(chance, 1, prompts, **tight)
    assert got == want
    assert eng.stats()["preemptions"] > 0
    assert eng.stats()["drafts"]["accepted"] > 0


def test_through_a_prefix_hit(chance):
    """The same 40-token prompt again: its full pages come from the prefix
    index in both groups (the drafter's layer's with the full group's),
    the tail prefills, and the tokens are the first run's."""
    prompt = _prompts(3, (40,))
    want, _ = _serve(chance, 0, prompt, prefix_cache=False)
    first, eng = _serve(chance, 1, prompt)
    again, _ = _serve(chance, 1, prompt + _prompts(4, (40,)), engine=eng)
    assert first == want and again[0] == want[0]
    pc = eng.stats()["prefix_cache"]
    assert pc["hits"] >= 1 and pc["hit_tokens"] >= 32


@pytest.mark.parametrize("eos", range(8))
def test_eos_on_either_of_two_tokens(chance, eos):
    """Whatever token ends a sequence, and wherever in a step's one or two
    tokens it falls, nothing is emitted after it."""
    want, _ = _serve(chance, 0, _prompts(5), eos_token_id=eos)
    got, _ = _serve(chance, 1, _prompts(5), eos_token_id=eos)
    assert got == want
    for toks in got:
        assert eos not in toks[:-1]


@pytest.mark.parametrize("new", [1, 2, 3, 4, 5, 6])
def test_a_length_that_falls_on_the_first_of_two_tokens(chance, new):
    want, _ = _serve(chance, 0, _prompts(6), new=new)
    got, eng = _serve(chance, 1, _prompts(6), new=new)
    assert got == want and all(len(t) == new for t in got)
    # the last token a sequence wants is never a draft's second
    assert eng.stats()["drafts"]["drafted"] <= 4 * max(0, new - 2)


def test_a_window_shorter_than_a_page(chance):
    """Pages of 32 under a window of 16: a page outlives the window, the
    draft's row lands in a page part of which is behind it."""
    kw = {"block_size": 32, "max_blocks": {"window": 12, "full": 12},
          "prefill_chunk": 24}
    want, _ = _serve(chance, 0, _prompts(7, (5, 50, 70)), new=40, **kw)
    got, eng = _serve(chance, 1, _prompts(7, (5, 50, 70)), new=40, **kw)
    assert got == want and eng.stats()["drafts"]["accepted"] > 0


def test_under_the_run_loop(chance):
    """The loop one step ahead: a step's rows take their tokens, their
    drafts and their positions from the step before on the device, whose
    acceptances the host has not read; both branches run, and no step is
    serial for a draft's sake."""
    dispatched = serving_metrics()["dispatched"]
    ahead = dispatched.value(order="ahead")
    want, _ = _serve(chance, 0, _prompts(8), loop=True)
    mid = dispatched.value(order="ahead")
    got, eng = _serve(chance, 1, _prompts(8), new=40, loop=True)
    assert got[0][:20] == want[0] and [t[:20] for t in got] == want
    d = eng.stats()["drafts"]
    assert 0 < d["accepted"] < d["drafted"]
    # most of the drafted engine's steps went out ahead
    assert dispatched.value(order="ahead") - mid > d["decode_seqs"] / 8
    assert mid > ahead
    drafts = serving_metrics()["drafts"]
    assert drafts.value(kind="drafted") >= d["drafted"]


@pytest.mark.parametrize("new", [2, 3, 7, 8])
def test_under_the_run_loop_the_length_is_kept(chance, new):
    """Ahead of an acceptance a sequence's last step may verify a draft
    whose second token it does not want: it is dropped."""
    want, _ = _serve(chance, 0, _prompts(10), new=new)
    got, _ = _serve(chance, 1, _prompts(10), new=new, loop=True)
    assert got == want


@pytest.mark.parametrize("impl", ["gather", "rpa"])
def test_under_the_run_loop_through_preemption_and_a_window(chance, impl):
    """Under the kernel the work list of a step planned ahead is built for
    the longer context and walks the window from where the shorter one's
    would (``build_step_maps(slack=)``)."""
    prompts = _prompts(2, (20, 21, 22, 50))
    tight = {"max_blocks": {"window": 16, "full": 14}, "attn_impl": impl}
    want, _ = _serve(chance, 0, prompts, new=30, **tight)
    got, eng = _serve(chance, 1, prompts, new=30, loop=True, **tight)
    assert got == want and eng.stats()["preemptions"] > 0


def test_a_windowed_walk_under_slack_starts_where_the_shorter_context_s_would():
    """A decode pair under a window of 8 in pages of 8. At context 23 its
    first token sees keys 16..23: the walk starts in page 2. If the context
    may turn out 22 (``slack`` 1: an acceptance not yet reported), that
    token may see key 15: the walk starts in page 1, and still ends where
    the longer context's does."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import build_step_maps
    kw = dict(total_tokens=8, tile_q=8, block_size=8, max_items=16,
              max_seqs=2, window=8)

    def pages(m):
        return sorted(int(b) for b in m.step_blk[:m.walked])
    assert pages(build_step_maps([0, 2], [25], **kw)) == [2, 3]
    assert pages(build_step_maps([0, 2], [25], slack=[1], **kw)) == [1, 2, 3]
    assert pages(build_step_maps([0, 2], [25], slack=[0], **kw)) == [2, 3]
    # no window: every page up to the longer context's last, as before
    kw["window"] = None
    assert pages(build_step_maps([0, 2], [25], slack=[1], **kw)) == \
        pages(build_step_maps([0, 2], [25], **kw)) == [0, 1, 2, 3]


def test_a_sampled_request_takes_no_draft(chance):
    pt.seed(11)
    want, _ = _serve(chance, 0, _prompts(9, (12,)), temperature=0.8)
    pt.seed(11)
    got, eng = _serve(chance, 1, _prompts(9, (12,)), temperature=0.8)
    assert got == want
    assert eng.stats()["drafts"]["drafted"] == 0
    assert eng.stats()["drafts"]["emitted"] == 19


def test_a_model_without_a_drafter_is_refused(chance):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    with pytest.raises(TypeError, match="states no drafter"):
        ServingEngine(llama, draft_tokens=1)
    bare = ExaoneMoeForCausalLM(ExaoneMoeConfig.tiny(
        vocab_size=8, num_nextn_predict_layers=0))
    with pytest.raises(TypeError, match="states no drafter"):
        ServingEngine(bare, draft_tokens=1, **ENGINE)
    with pytest.raises(ValueError, match="want 0 or 1"):
        ServingEngine(chance, draft_tokens=2, **ENGINE)
    assert "drafts" not in ServingEngine(chance, **ENGINE).stats()


def _shape_of(text):
    """The opcodes of a lowered step with their result types, counted."""
    ops = re.findall(r"= ((?:stablehlo|func|chlo)\.[\w.]+)[^\n]*?-> ([^\n{]+)",
                     text)
    return collections.Counter(ops)


def test_without_drafts_the_step_is_that_of_a_model_without_a_drafter(
        chance):
    """``draft_tokens=0`` over a model that publishes a drafter lowers to
    the opcodes and result types of the same model built without one (the
    drafter's leaves ride along as unused inputs); with drafts the step is
    another program, of more rows and one layer more."""
    pt.seed(0)
    bare = ExaoneMoeForCausalLM(ExaoneMoeConfig.tiny(
        vocab_size=8, num_nextn_predict_layers=0))
    bare.eval()
    plain = ServingEngine(bare, **ENGINE)._lowered_step().as_text()
    undrafted = ServingEngine(chance, **ENGINE)._lowered_step().as_text()
    drafted = ServingEngine(chance, draft_tokens=1,
                            **ENGINE)._lowered_step().as_text()
    assert _shape_of(undrafted) == _shape_of(plain)
    assert _shape_of(drafted) != _shape_of(plain)
    assert "tensor<4x4xi32>" in drafted      # token, after, accepted, draft


def test_a_rejected_draft_s_row_left_visible_fails(chance):
    """Planted: after a rejected draft the sequence moves on as if the
    draft's row were confirmed, so the next step writes beyond it and reads
    the stale row. The tokens are no longer the undrafted engine's."""
    want, _ = _serve(chance, 0, _prompts())
    eng = ServingEngine(chance, draft_tokens=1, **ENGINE)
    harvest = eng._harvest

    def faulty():
        flight = eng._flights[0] if eng._flights else None
        toks = np.asarray(flight.tokens) if flight else None
        harvest()
        for i, (seq, _, _, _, drafts) in enumerate(
                flight.entries if flight else ()):
            if drafts and not toks[2, i] and not seq.done:
                seq.num_cached += 1
    eng._harvest = faulty
    handles = [eng.submit(p, max_new_tokens=20) for p in _prompts()]
    eng.run_until_idle()
    assert [h.token_ids for h in handles] != want
