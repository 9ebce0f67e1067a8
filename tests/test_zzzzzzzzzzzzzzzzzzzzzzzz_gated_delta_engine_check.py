"""The gated delta-rule layer kind through ``EngineCore``, second file
(ISSUE 49 and its review): preemption by recompute, and the benchmark's
check, which reads the slot a sequence has left and holds the state
itself.  float32 on the CPU at toy widths (``gdn_common.py``)."""

import numpy as np

from gdn_common import (TINY, builder, chunks_of_eight, make_engine, model,
                        prompt_of, ref, serve)     # noqa: F401  (fixtures)


def test_preemption_by_recompute_gives_the_same_tokens(model):
    from paddle_tpu.serving.request import SamplingParams

    calm = make_engine(model)
    prompts = [prompt_of(14, seed=s) for s in range(4)]
    want = [serve(calm, p, 24).output_tokens for p in prompts]
    # 5 common blocks beside the 4 slots' own: four rows of 39 tokens
    # (3 blocks each) do not fit
    tight = make_engine(model, num_blocks=10)
    reqs = [tight.add_request(p, SamplingParams(max_new_tokens=25,
                                                temperature=0.0))
            for p in prompts]
    held = []
    for _ in range(400):
        if all(r.finished for r in reqs):
            break
        tight.step()
        held.append(tight.kv.state_slots_held)
    reg, labels = tight.metrics.registry, tight.metrics.labels
    assert reg.counter("serving_preemptions_total", **labels).value > 0
    assert [r.output_tokens for r in reqs] == want
    # a preempted request gave its slot back with its pages
    assert min(held[:-1]) < 4 and held[-1] == 0
    assert tight.kv.num_free == 9


# --- the benchmark's check holds the state itself (REVIEW of PR 49) ------------------

def test_the_check_reads_the_slot_a_sequence_left_and_a_bf16_state_fails_it(
        model, builder, ref, monkeypatch):
    """Logits after five layers cannot see a recurrent state's precision
    on the chip, so the cell's check compares every delta-rule layer's
    slot with the reference's ``S_T``: through the launcher's own
    ``check_reference``, the float32 state agrees to rounding, and the same
    state rounded to bfloat16 after every launch (what a bf16 slot pool
    would hold) fails BY THE STATE while its logits stay inside loose
    limits."""
    import gc

    import jax

    from benchmarks import launcher
    from paddle_tpu.models import gated_delta_moe_mla as kind

    cfg = dict(TINY, check={
        "prompt_lens": [21, 12], "decode_steps": 3, "atol": 0.05,
        "rms_rel": 0.05, "margin_eps": 0.0, "max_left_out_share": 0.0,
        "max_left_out_a_prompt": 0.5,
        "state_rel": {"0": 1e-4, "1": 1e-4, "2": 1e-3, "4": 1e-3}})

    def check():
        gc.collect()            # no engine of a test before this one
        eng = make_engine(model)
        return launcher.check_reference(
            eng, launcher.Probe(eng, TINY["vocab_size"]), model, builder,
            ref, cfg, 11)

    res = check()
    assert res["ok"] and res["rows"] == 8, res
    assert set(res["state_rel_err"]) == {"0", "1", "2", "4"}
    assert max(res["state_rel_err_worst_head"].values()) < 1e-4
    step, chunked = kind.gated_delta_step, kind.gated_delta_chunked

    def rounded(o_s):
        return o_s[0], jax.lax.reduce_precision(o_s[1], 8, 7)

    monkeypatch.setattr(kind, "gated_delta_step",
                        lambda *x: rounded(step(*x)))
    monkeypatch.setattr(kind, "gated_delta_chunked",
                        lambda *x, **k: rounded(chunked(*x, **k)))
    res = check()
    assert not res["ok"], res
    assert res["max_abs_diff"] <= 0.05 and res["rms_rel"] <= 0.05
    assert res["state_rel_err"]["0"] > 1e-3


def test_the_check_refuses_a_prompt_most_of_whose_rows_are_left_out(ref):
    rows, vocab = 8, 5                  # two prompts, a prefill and 3 steps
    want = np.ones((rows, vocab), np.float32)
    got = want.copy()
    got[5:] += 1.0                      # three of the second prompt's four
    ref._tie._CHECK.clear()
    ref._tie._CHECK.update(prompt_lens=[9, 9], decode_steps=3,
                           max_left_out_a_prompt=0.5)
    near_ties = np.full(rows, 1e-4)
    res = ref.compare(got, want, 0.5, 0.5, margins=near_ties,
                      margin_eps=1e-3, max_left_out_share=0.5)
    assert res["left_out_share"] == 0.375 and res["rows_compared"] == 5
    assert res["left_out_a_prompt"] == [0, 3] and not res["ok"], res
    got[6] = 1.0                        # two of four: not most
    res = ref.compare(got, want, 0.5, 0.5, margins=near_ties,
                      margin_eps=1e-3, max_left_out_share=0.5)
    assert res["left_out_a_prompt"] == [0, 2] and res["ok"], res
