"""The DeepSeek-V2-Lite cell (``dsv2lite-chat``) on the CPU at a test's
size: its files load by name, its work functions count by hand, a sound
run is correct, and the control and each fault planted in the timed path
(a stale latent cache, a token altered, one held expert's output left out)
come out not correct."""
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness, traffic, work_mla_moe  # noqa: E402
from perfbench.control import readings  # noqa: E402

DATA = ROOT / "perfbench" / "tests" / "data"
CELL = "dsv2lite-chat"
METRICS = ["mla_decode_roofline.dsv2", "moe_gmm_roofline.dsv2",
           "decode_step_ms.dsv2", "serve_mfu.dsv2"]


def _load(name):
    return json.loads((DATA / name).read_text())


def run(seed=11, seconds=0.3):
    return harness.run_cell(CELL, seed, seconds, False, time.perf_counter(),
                            require_tpu=False,
                            config_override=_load("tiny-dsv2.json"),
                            traffic_override=_load("tiny-chat-ep4.json"))


def test_cell_files_load_by_name():
    bench = harness.load_benchmark()
    wl, cfg = harness.cell_entries(bench, CELL)
    config = harness.load_json(cfg["file"])
    assert config["path"] == "serving_mla_moe" and wl["chips"] == 1
    m, dep = harness.path_module("serving_mla_moe").published(config), \
        config["deployment"]
    assert (m["n_routed_experts"], dep["n_routed_experts"]) == (16, 64)
    assert cfg["reduced"] == ["n_routed_experts"]
    assert traffic.load(wl["traffic"])["batch"] == 64
    traced = [x["name"] for x in harness.cell_metrics(bench, CELL, True)]
    assert traced == METRICS
    for name in METRICS:
        assert harness.metric_reader(name)({"path": None}) is None
        # a Qwen3 serving record has nothing for them either
        assert harness.metric_reader(name)({"path": "serving", "batches": []}) is None


TINY = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 2, "kv_lora_rank": 4, "qk_nope_head_dim": 2,
        "qk_rope_head_dim": 2, "v_head_dim": 2, "hidden_size": 8,
        "intermediate_size": 16, "moe_intermediate_size": 4,
        "n_routed_experts": 2, "n_shared_experts": 1, "vocab_size": 10}


def test_mla_decode_work_by_hand():
    # 3 layers x 2 heads; latent 4 + 2 = 6 wide; contexts 3 and 5:
    # flops 2 * 2 * c * (6 + 4) = 40c, bytes (6c + 2 * 6) * 2
    flops, nbytes = work_mla_moe.mla_decode_work(TINY, [3, 5])
    assert flops == 3 * 40 * (3 + 5)
    assert nbytes == 3 * ((18 + 12) * 2 + (30 + 12) * 2)


def test_moe_gmm_work_by_hand():
    # 2 expert layers; 10 rows in 5 steps: 1 row a layer and step touches
    # 1 expert of 3 * 8 * 4 * 2 = 192 bytes; rows in and out 10 * 2 * 8 * 2
    flops, nbytes = work_mla_moe.moe_gmm_work(TINY, 10, 5)
    assert flops == 6 * 8 * 4 * 10
    assert nbytes == 5 * 2 * 1 * 192 + 320
    # with many rows a step, at most the held experts' weights
    _, nbytes = work_mla_moe.moe_gmm_work(TINY, 1000, 5)
    assert nbytes == 5 * 2 * 2 * 192 + 1000 * 32


def test_model_flops_by_hand():
    # one prompt of 1 token, no decode, no routed rows: per layer the
    # expanded projections 2*8*2*4 + 2*8*6 + 2*2*2*8 + 2*4*2*4 = 352 and one
    # causal pair 2*2*6 = 24; dense 6*8*16, shared + router 2 * (6*8*4 + 2*8*4)
    f = work_mla_moe.model_flops(TINY, 4, [1], [], 0)
    assert f == 3 * (352 + 24) + 6 * 8 * 16 + 2 * (192 + 64) + 2 * 8 * 10


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"serve_tokens_per_s", "token_gap_p95_ms", "setup_s"} <= set(out["metrics"])


def test_records_rows_routed_to_held_experts(monkeypatch):
    mod = harness.path_module("serving_mla_moe")
    seen = {}
    window = mod.Cell.window

    def keep(self, trace_dir=None):
        seen["rec"] = window(self, trace_dir)
        return seen["rec"]

    monkeypatch.setattr(harness, "path_module", lambda name: mod)
    monkeypatch.setattr(mod.Cell, "window", keep)
    run(seconds=0.0)
    rec = seen["rec"]
    cfg = mod.published(_load("tiny-dsv2.json"))
    b = rec["batches"][0]
    rows = b["moe_rows_held"]
    # every token of every step meets 6 of the 16 experts; 4 are held
    tokens = len(b["outs"]) * (b["L"] + max(b["outs"]) - 1)
    assert 0 < rows <= tokens * cfg["num_experts_per_tok"] * 2
    assert 0 < rec["moe_rows_max"] <= rows


def test_control_is_not_correct():
    config = _load("tiny-dsv2.json")
    (line,) = readings(CELL, [5], 0.0, require_tpu=False, config=config,
                       mix=_load("tiny-chat-ep4.json"))
    lim = config["limits"]
    assert all(v <= lim[k] for k, v in line["program"].items()), line
    assert line["control"]["token_gap"] > lim["token_gap"], line


def test_stale_latent_cache_is_not_correct(monkeypatch):
    from repro.runtime import steps

    decode_fn = steps.decode_fn

    def stale(cfg):
        f = decode_fn(cfg)

        def decode(params, cache, tok, pos):
            logits, new = f(params, cache, tok, pos)
            return logits, dict(new, pattern=cache["pattern"])
        return decode

    monkeypatch.setattr(steps, "decode_fn", stale)
    out = run()
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > 0.02


def test_token_altered_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from repro.runtime import steps

    def altered(logits):
        return ((jnp.argmax(logits, -1) + 1) % logits.shape[-1]).astype(
            jnp.int32)

    monkeypatch.setattr(steps, "greedy_sample", altered)
    out = run()
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > 0.02


def test_held_expert_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from repro.models import moe

    layout = moe.held_layout

    def drop_first(m, idx, tm):
        lay = layout(m, idx, tm)
        first = (idx.reshape(-1) == m.expert_offset)
        return dict(lay, dest=jnp.where(first, lay["slots"], lay["dest"]))

    monkeypatch.setattr(moe, "held_layout", drop_first)
    out = run()
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > 0.02
