"""The seam between ``EngineCore`` and the kinds of decoder layer it serves
(ISSUE 48): a kind brings its launch telemetry (``ops.paged_attention
.LaunchTelemetry``: an array that rides the launch, integers on
``engine.build`` and ``engine.fetch``, its own ``/metrics`` series, a
``forget``) and the class of its cache object (``CacheSpec.cache``), and the
engine iterates what the model's layers name.  A toy kind defined HERE is
served with ``serving/engine.py`` knowing nothing of it; and for the six
tiny configurations the names of the integers and of the series are pinned,
as literals taken from the commit before the seam was cut (b752acf)."""

import inspect

import numpy as np
import pytest

import jax.numpy as jnp


def make_engine(model, **kw):
    from paddle_tpu.serving import EngineConfig, EngineCore, SchedulerConfig

    return EngineCore(model, config=EngineConfig(
        num_blocks=64, block_size=16, dtype=jnp.float32, prefix_cache=False,
        scheduler=SchedulerConfig(max_num_seqs=4), **kw))


def serve(eng, prompt, new_tokens):
    """One request to its end; the integers each ``engine.build`` and
    ``engine.fetch`` carried, in order."""
    from paddle_tpu.serving.request import SamplingParams

    seen, real = {"engine.build": [], "engine.fetch": []}, eng.tracer.phase

    def phase(name, recorder=None, **ints):
        if name in seen:
            seen[name].append(ints)
        return real(name, recorder, **ints)

    eng.tracer.phase = phase
    req = eng.add_request(prompt, SamplingParams(max_new_tokens=new_tokens,
                                                 temperature=0.0))
    for _ in range(new_tokens + 8):
        if req.finished:
            break
        eng.step()
    assert req.finished
    eng.tracer.phase = real
    return req, seen


def series(eng):
    return {line.split("{")[0].split(" ")[0]
            for line in eng.metrics.registry.prometheus_text().splitlines()
            if line.startswith("serving_")}


# --- a kind the engine has never heard of ------------------------------------------

def toy_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaDecoderLayer
    from paddle_tpu.ops.paged_attention import LaunchTelemetry

    class ToyCount(LaunchTelemetry):
        """``toy_rows`` on ``engine.build``; the tokens the toy layers saw
        ride the launch and become ``toy_tokens`` on ``engine.fetch``;
        ``serving_toy_launches_total``; the requests let go."""

        def __init__(self, layers, view):
            super().__init__(layers, view)
            self.launches = view.registry.counter(
                "serving_toy_launches_total", help="launches read",
                **view.labels)
            self.programs, self.spans, self.gone = [], [], []

        def traced(self):
            seen = [layer.saw for layer in self.layers]
            for layer in self.layers:
                layer.saw = None
            return sum(seen)

        def build_ints(self, view, rows, reqs):
            self.programs.append((view.program, len(reqs)))
            return {"toy_rows": rows}

        def fetch_ints(self, program, host_array):
            self.launches.inc()
            self.spans.append((program, self.view.span))
            return {"toy_tokens": int(host_array)}

        def forget(self, request_id):
            self.gone.append(request_id)

    class ToyLayer(LlamaDecoderLayer):
        """A dense layer that counts the tokens it is run over."""

        telemetry = (ToyCount,)
        saw = None

        def forward(self, x, cache=None, pos=None):
            self.saw = jnp.sum(jnp.isfinite(x._value[..., 0])).astype(
                jnp.int32)
            return super().forward(x, cache=cache, pos=pos)

    class ToyConfig(LlamaConfig):
        def make_decoder_layer(self, layer_idx):
            return ToyLayer(self, layer_idx)

    paddle.seed(3)
    return LlamaForCausalLM(ToyConfig(**LlamaConfig.tiny(
        num_hidden_layers=2).__dict__)), ToyCount


def test_a_kind_defined_here_is_served_and_the_engine_does_not_know_it():
    from paddle_tpu.serving import engine as engine_module

    model, ToyCount = toy_model()
    eng = make_engine(model)
    (toy,) = eng._telemetry
    assert isinstance(toy, ToyCount) and len(toy.layers) == 2
    req, seen = serve(eng, list(range(1, 11)), 3)
    # engine.build: one prefill launch, two decode launches of one row
    assert [b["toy_rows"] for b in seen["engine.build"]] == [1, 1, 1]
    assert toy.programs == [("prefill", 1), ("decode", 1), ("decode", 1)]
    # the prompt's span (from 0, ten tokens) is there when its launch is read
    assert toy.spans[0] == ("prefill", (0, 10))
    # engine.fetch: what rode the launch -- two layers over a bucket of 16
    # prompt tokens, then over one row
    assert [f["toy_tokens"] for f in seen["engine.fetch"]] == [32, 2, 2]
    assert all(layer.saw is None for layer in toy.layers)
    # its series, under the engine's registry; and the row let go
    assert "serving_toy_launches_total 3" in \
        eng.metrics.registry.prometheus_text().replace("3.0", "3")
    assert toy.gone == [req.request_id]
    assert "toy" not in inspect.getsource(engine_module).lower()
    # the same tokens as the plain dense model: the kind computes nothing
    plain = make_engine(type(model)(type(model.config).__mro__[1](
        **model.config.__dict__)))
    plain.model.set_state_dict(model.state_dict())
    assert plain._telemetry == []
    assert list(serve(plain, list(range(1, 11)), 3)[0].output_tokens) \
        == list(req.output_tokens)


# --- the names, as the parent commit wrote them -------------------------------------

BASE_SERIES = set("""
    serving_admission_rejected_total serving_ahead_dropped_rows_total
    serving_ahead_launches_total serving_block_lifetime_steps_bucket
    serving_block_lifetime_steps_count serving_block_lifetime_steps_sum
    serving_bucket_utilization_bucket serving_bucket_utilization_count
    serving_bucket_utilization_sum serving_burst_jit_traces_total
    serving_burst_launches_total serving_burst_length_bucket
    serving_burst_length_count serving_burst_length_sum
    serving_burst_step_seconds_bucket serving_burst_step_seconds_count
    serving_burst_step_seconds_sum serving_burst_tokens_total
    serving_chunked_prefill_steps_total serving_collective_seconds_bucket
    serving_collective_seconds_count serving_collective_seconds_sum
    serving_compile_seconds_total serving_compiles_total
    serving_decode_itl_seconds_bucket serving_decode_itl_seconds_count
    serving_decode_itl_seconds_sum serving_decode_jit_traces_total
    serving_decode_step_seconds_bucket serving_decode_step_seconds_count
    serving_decode_step_seconds_sum serving_e2e_seconds_bucket
    serving_e2e_seconds_count serving_e2e_seconds_sum
    serving_engine_steps_total serving_greedy_launches_total
    serving_greedy_tokens_total serving_host_roundtrips_total
    serving_inter_token_latency_seconds_bucket
    serving_inter_token_latency_seconds_count
    serving_inter_token_latency_seconds_sum serving_kv_bytes_per_token
    serving_kv_pool_occupancy serving_lifecycle_events_dropped_total
    serving_lifecycle_events_total serving_logits_fetch_bytes_total
    serving_logits_fetches_total serving_mp_shards serving_num_running
    serving_padding_tokens_total serving_pool_allocated_blocks
    serving_pool_available_blocks serving_pool_evictions_total
    serving_pool_free_blocks serving_pool_reuse_blocks
    serving_preemptions_total serving_prefill_jit_traces_total
    serving_prefill_seconds_bucket serving_prefill_seconds_count
    serving_prefill_seconds_sum serving_prefill_step_seconds_bucket
    serving_prefill_step_seconds_count serving_prefill_step_seconds_sum
    serving_prefill_tokens_computed_total
    serving_prefix_cache_evictions_total
    serving_prefix_cache_hit_tokens_total
    serving_prefix_cache_miss_tokens_total serving_prefix_cached_token_ratio
    serving_queue_depth serving_queue_wait_seconds_bucket
    serving_queue_wait_seconds_count serving_queue_wait_seconds_sum
    serving_ragged_jit_traces_total serving_recompute_prefills_total
    serving_requests_admitted_total serving_requests_finished_abort_total
    serving_requests_finished_eos_total
    serving_requests_finished_length_total
    serving_requests_finished_replica_failed_total
    serving_requests_finished_timeout_total serving_reuse_hit_depth_bucket
    serving_reuse_hit_depth_count serving_reuse_hit_depth_sum
    serving_sampled_tokens_total serving_sampling_launches_total
    serving_scheduled_tokens_total serving_slo_good_total serving_slo_total
    serving_step_seconds_bucket serving_step_seconds_count
    serving_step_seconds_sum serving_time_to_first_token_seconds_bucket
    serving_time_to_first_token_seconds_count
    serving_time_to_first_token_seconds_sum
    serving_unified_step_seconds_bucket serving_unified_step_seconds_count
    serving_unified_step_seconds_sum serving_unified_steps_total
""".split())

# configuration -> (integers on engine.build beside ``rows``, on engine.fetch
# beside ``bytes``, series beside BASE_SERIES)
NAMES = {
    "llama_dense": (
        set(),
        set(),
        set()),
    "moe_mla": (
        set(),
        {"moe_assignments", "moe_decode", "moe_experts_touched", "moe_max_load", "moe_streamed"},
        {"serving_moe_assignments_total", "serving_moe_experts_touched_total", "serving_moe_load_max_over_mean", "serving_moe_streamed_launches_total"}),
    "mamba_hybrid": (
        {"state_rows", "state_slots_held"},
        set(),
        {"serving_state_bytes_per_sequence", "serving_state_slots_capacity", "serving_state_slots_held"}),
    "window_moe": (
        {"state_rows", "state_slots_held", "window_tokens"},
        {"moe_assignments", "moe_decode", "moe_experts_touched", "moe_held_touched", "moe_max_load", "moe_pairs_held", "moe_streamed"},
        {"serving_moe_assignments_total", "serving_moe_experts_touched_total", "serving_moe_held_pair_share", "serving_moe_load_max_over_mean", "serving_moe_pairs_held_total", "serving_moe_streamed_launches_total", "serving_state_bytes_per_sequence", "serving_state_slots_capacity", "serving_state_slots_held"}),
    "hc_moe_mla": (
        {"hc_streams"},
        {"hc_entries", "hc_res_clamped", "hc_sinkhorn_residual_ppb", "moe_assignments", "moe_decode", "moe_experts_touched", "moe_max_load", "moe_streamed"},
        {"serving_hc_res_clamped_total", "serving_hc_sinkhorn_residual", "serving_moe_assignments_total", "serving_moe_experts_touched_total", "serving_moe_load_max_over_mean", "serving_moe_streamed_launches_total"}),
    "eva": (
        {"eva_pool_tiles", "eva_pool_tiles_seen", "eva_ring_tokens", "eva_rows_held", "eva_summary_rows", "eva_windows_closed", "state_rows", "state_slots_held"},
        set(),
        {"serving_eva_pool_tiles_seen_total", "serving_eva_pool_tiles_total", "serving_eva_summary_rows_held", "serving_eva_windows_closed_total", "serving_state_bytes_per_sequence", "serving_state_slots_capacity", "serving_state_slots_held"}),
}


def tiny_config(name):
    from paddle_tpu import models as M

    return {"llama_dense": lambda: M.LlamaConfig.tiny(num_hidden_layers=2),
            "moe_mla": M.MoEMLAConfig.tiny,
            "mamba_hybrid": M.HybridMambaConfig.tiny,
            "window_moe": M.WindowMoEConfig.tiny,
            "hc_moe_mla": M.HCMoEMLAConfig.tiny,
            "eva": M.EvaConfig.tiny}[name]()


@pytest.mark.parametrize("name", sorted(NAMES))
def test_the_integers_and_series_of_each_tiny_configuration(name):
    """After one prefill and two decode steps: the SET of integer names a
    phase and the set of ``serving_*`` series, each what the parent
    commit's engine wrote."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    build, fetch, extra = NAMES[name]
    paddle.seed(7)
    eng = make_engine(LlamaForCausalLM(tiny_config(name)))
    _, seen = serve(eng, list(range(1, 11)), 3)
    assert len(seen["engine.build"]) == len(seen["engine.fetch"]) == 3
    assert set().union(*seen["engine.build"]) == build | {"rows"}
    assert set().union(*seen["engine.fetch"]) == fetch | {"bytes"}
    assert series(eng) == BASE_SERIES | extra
    # a decode launch carries every integer of its phase; a prefill launch
    # those that are not a decode launch's alone
    assert all(set(b) == build | {"rows"} for b in seen["engine.build"][1:])
    assert set(seen["engine.build"][0]) == {
        k for k in build if k.startswith(("state_", "hc_"))}
    assert all(set(f) == fetch | {"bytes"} for f in seen["engine.fetch"])
