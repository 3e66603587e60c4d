"""The looped configuration's benchmark files, on the CPU: the configuration
states the published sizes and cuts nothing, the family's leaf table is the
program's tree at full size (abstract: nothing is allocated), the byte count
``decode_bandwidth_share`` divides by is right by a hand count, the cell's
traffic is the chat cell's one trace, and the two new readers read what the
program writes and nothing where it writes nothing."""
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, host_trace, traffic  # noqa: E402

CELL = "serve_ouro_2_6b_chat"
TINY = {"d_model": 64, "n_layers": 3, "n_loops": 3, "n_heads": 4, "n_kv_heads": 4,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256, "param_dtype": "bfloat16"}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def test_configuration_states_the_published_sizes_and_cuts_nothing(cell):
    config, model = cell["config"], cell["config"]["model"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == [] and config["reduced"] == []
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    # the published config.json's own keys, at the top level and unchanged
    published = {"hidden_size": 2048, "num_hidden_layers": 48, "total_ut_steps": 4,
                 "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
                 "intermediate_size": 5632, "vocab_size": 49152, "rope_theta": 1000000,
                 "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
                 "early_exit_threshold": 1, "tie_word_embeddings": False}
    assert {k: config[k] for k in published} == published
    # ... and the same sizes under the program's keys
    assert (model["d_model"], model["n_layers"], model["n_loops"], model["n_heads"],
            model["n_kv_heads"], model["head_dim"], model["d_ff"], model["vocab_size"]) \
        == (2048, 48, 4, 16, 16, 128, 5632, 49152)
    assert model["param_dtype"] == "bfloat16" and model["exit_threshold"] == 1.0
    assert config["assumed"] and all(isinstance(v, str) for v in config["assumed"].values())


def test_leaf_table_is_the_programs_tree_at_full_size(cell):
    config, model = cell["config"], cell["config"]["model"]
    harness.check_configuration(config)  # the abstract tree: nothing is allocated
    ref = harness.load_reference(config)
    held = sum(math.prod(s) for s, _ in ref.leaf_table(model).values())
    # what memory holds, and what a token is multiplied by: 3.7 times as much
    assert held == harness.model_config(config).num_params == config["parameters"] \
        == 2_667_974_657
    assert ref.active_params(model) == config["active_parameters"] == \
        4 * 48 * 51_380_224 + 2048 * 49152
    assert ref.attention_flops_per_position(model) == 4 * 192 * 2048
    # a cached position costs one K and one V row in each of 192 entries
    assert ref.kv_bytes_per_position(model) == 1_572_864


def test_decode_read_bytes_against_a_hand_count(cell):
    ref = harness.load_reference(cell["config"])
    # one layer: q, k, v, o of 64 x 64 and gate, up, down of 64 x 128
    layer = 4 * 64 * 64 + 3 * 64 * 128
    assert ref.layer_matrix_params(TINY) == layer == 40960
    # a tick reads the 3 layers once a pass (3 passes) and the head, 2 bytes
    # each, and K and V (4 heads of 16, 2 bytes) of every live position in
    # 9 entries
    weights_bytes = (3 * 3 * layer + 64 * 256) * 2
    per_position = 9 * 2 * 4 * 16 * 2
    assert ref.decode_read_bytes(TINY, 0) == weights_bytes == 770048
    assert ref.decode_read_bytes(TINY, 100) == weights_bytes + 100 * per_position
    assert ref.decode_read_bytes(dict(TINY, param_dtype="float32"), 0) == 2 * weights_bytes
    # at the cell's size: 4 x 4.93 GB of layers + 0.2 GB of head
    model = cell["config"]["model"]
    assert ref.decode_read_bytes(model, 0) == 2 * (192 * 51_380_224 + 2048 * 49152)


def test_traffic_is_the_chat_cells_one_trace_at_another_rate(cell):
    mine = cell["traffic"]
    chat = json.loads((ROOT / "benchmark/traffic/alpaca_open_poisson.json").read_text())
    for key in ("kind", "prompt_len", "output_len", "sampling", "warmup", "reference"):
        assert mine[key] == chat[key], key
    assert mine["engine"] == dict(chat["engine"], cache_len=512, page_pool_tokens=2560)
    assert mine["params_dtype"] == "bfloat16" and mine["engine"]["page_pool_tokens"] % 256 == 0
    a = traffic.open_loop_requests(dict(mine, rate_per_s=1.6), 5, 51.0, 49152)
    b = traffic.open_loop_requests(dict(chat, rate_per_s=1.6), 5, 51.0, 50304)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    longest = max(len(r.prompt) + r.max_new_tokens for r in
                  traffic.open_loop_requests(mine, 5, 51.0, 49152))
    assert longest <= mine["engine"]["cache_len"]


def _spans(pages):
    return [(i, "engine", "decode_step", 10.0 + i, 10.05 + i, {"tick": i, "pages_in_use": p})
            for i, p in enumerate(pages)]


def test_kv_pool_reader_takes_the_median_over_the_windows_decode_steps():
    read = harness.load_reader("kv_pool_in_use_p50").read
    mix = {"engine": {"page_pool_tokens": 2560, "page_size": 16}}
    ctx = {"spans": _spans([40, 80, 48]) + [(9, "engine", "decode_step", 1.0, 1.05,
                                             {"tick": 9, "pages_in_use": 160})],
           "mix": mix, "t0": 5.0, "t_end": 20.0}
    assert read(ctx) == pytest.approx(100.0 * 48 / 160)
    # the parent's spans carry no such attribute: nothing to read, no error
    old = [(0, "engine", "decode_step", 10.0, 10.05, {"tick": 0, "active": 3})]
    assert read(dict(ctx, spans=old)) is None
    assert read({}) is None


def test_bandwidth_reader_counts_live_positions_and_needs_a_capture(monkeypatch):
    reader = harness.load_reader("decode_bandwidth_share")
    records = [
        {"prompt": [1] * 20, "prefill_done_at": 9.0, "finished_at": None,
         "token_times": [9.0, 10.2, 10.4]},           # decoding at 10.3: 20 + 2 cached
        {"prompt": [1] * 7, "prefill_done_at": 10.35, "finished_at": None,
         "token_times": [10.35]},                     # not yet prefilled at 10.3
        {"prompt": [1] * 5, "prefill_done_at": 8.0, "finished_at": 10.1,
         "token_times": [8.0, 9.0]},                  # finished before 10.3
    ]
    spans = [(0, "engine", "decode_step", 10.3, 10.35, {"tick": 0, "loops": 3}),
             (1, "engine", "decode_step", 12.0, 12.05, {"tick": 1, "loops": 3})]  # outside the capture
    ctx = {"spans": spans, "records": records, "traced": (10.0, 11.0)}
    assert reader.live_positions(ctx) == [(3, 22)]
    # no capture on disk, an untraced run, a model of another family: None
    monkeypatch.setattr(host_trace, "newest_xplane", lambda root=None: None)
    full = dict(ctx, trace={"events": {0: []}}, model=dict(TINY),
                peak={"bytes_per_s": 819e9})
    assert reader.read(full) is None
    assert reader.read(dict(full, model={"d_model": 64})) is None
    assert reader.read({}) is None
    # with programs in a capture: bytes over bandwidth over their mean time
    monkeypatch.setattr(host_trace, "load", lambda path=None: {"modules": {0: [
        ("jit__fused_step_impl(123)", 0.0, 2e-5, 1), ("jit__fused_step_impl(123)", 1.0, 1.00004, 2),
        ("jit__paged_chunk_prefill_impl(9)", 2.0, 2.5, 3)]}})
    ref = harness.load_reference({"reference": "benchmark/reference/ouro_looplm.py"})
    want = 100.0 * (ref.decode_read_bytes(TINY, 22) / 819e9) / 3e-5
    assert reader.read(full) == pytest.approx(want)
    # a program that does not say how many passes it ran (the parent serves
    # this configuration as a one-pass model): nothing to read, no error
    old = [(0, "engine", "decode_step", 10.3, 10.35, {"tick": 0, "active": 1})]
    assert reader.read(dict(full, spans=old)) is None
