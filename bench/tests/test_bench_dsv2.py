"""The served DeepSeek-V2 system on a small cell of its own: a sound run
agrees with the plain reference, the reference in fp8 put in the program's
place does not, and a program that leaves out one held expert's term is
caught."""
import json

import pytest

from bench.tests import cells


def small_root(tmp):
    """``serve.dsv2.small``: the cell's configuration at widths a CPU test
    run holds (hidden 64, 4 heads, latent 16 + rotary 8, 4 held experts of
    16 routed over 4 groups, best 2 groups, top 3), its traffic at 4 rows.
    The limit on the mean untied gap is this size's own, set between its
    readings on the CPU with the cell's tie rule (0.05 logits) and the
    float32 router: the program read at most 0.00089 and the fp8 control
    at least 0.0456 over seven seeds (a held expert left out: 0.23-0.34
    on four)."""
    cfg = cells._load("bench/configs/deepseek-v2-l5-e8.json")
    cfg.update(hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=4,
               q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
               num_hidden_layers=3, n_routed_experts=4, n_group=4,
               topk_group=2, num_experts_per_tok=3, vocab_size=1000,
               held_experts=[0, 4],
               check={"served_logit_gap_mean": 0.006,
                      "route_tie_logit": 0.05})
    cfg["source_values"] = dict(cfg["source_values"], n_routed_experts=16)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], factor=4,
                               original_max_position_embeddings=32)
    chat = cells._load("bench/traffic/chat.r128.json")
    chat.update(rows=4, prompt_len=[4, 12], new_tokens=[8, 16], n_shapes=4,
                max_len=32)
    (tmp / "bench/configs").mkdir(parents=True, exist_ok=True)
    (tmp / "bench/traffic").mkdir(parents=True, exist_ok=True)
    (tmp / "bench/configs/small-dsv2.json").write_text(json.dumps(cfg))
    (tmp / "bench/traffic/small.chat128.json").write_text(json.dumps(chat))
    cells.copy_kinds(tmp)
    spec = {"command": cells.SPEC["command"], "paths": cells.SPEC["paths"],
            "run_seconds": 1,
            "configs": [{"name": "small-dsv2", "source": "test",
                         "file": "bench/configs/small-dsv2.json",
                         "reduced": [], "why": "test"}],
            "workloads": [{"name": "serve.dsv2.small", "config": "small-dsv2",
                           "traffic": "small.chat128", "chips": 1,
                           "why": "test"}],
            "end_to_end": [{"name": "decode_tokens_per_s", "unit": "1",
                            "better": "higher", "bound": 0.25,
                            "source": "host_clock"},
                           {"name": "setup_s", "unit": "s",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock"}],
            "per_layer": []}
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("dsv2"))


def _limit(root):
    return json.loads((root / "bench/configs/small-dsv2.json").read_text())[
        "check"]["served_logit_gap_mean"]


def test_sound_run_agrees_with_the_reference(root):
    line = cells.run(root, "serve.dsv2.small")
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"served_logit_gap_mean",
                                   "wrong_circuits", "shed_tenants"}


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_in_fp8_exceeds_the_limit(root, seed):
    got = cells.control(root, "serve.dsv2.small", seed)
    assert got["program"]["served_logit_gap_mean"] <= _limit(root)
    assert got["control"]["served_logit_gap_mean"] > _limit(root)


def test_a_held_expert_left_out_is_caught(root, monkeypatch):
    from repro.models import moe
    route = moe.route

    def without_first(logits, c):
        w, e = route(logits, c)
        return w * (e != c.held[0]), e
    monkeypatch.setattr(moe, "route", without_first)
    line = cells.run(root, "serve.dsv2.small")
    assert line["correct"] is False, line["checks"]


def test_tie_margin_covers_every_flip_of_the_held_experts():
    """The reference's ``held_margin`` at the cell's router shape (160
    experts, 8 groups, best 3, top 6, experts 0-7 held): logits moved by
    at most ``a`` each change which held experts are chosen only for
    tokens whose margin is under ``2 a``."""
    import jax.numpy as jnp
    import numpy as np
    from bench.reference.deepseek_v2 import held_margin, route
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2000, 160)).astype(np.float32)
    margin = np.asarray(held_margin(jnp.asarray(logits), 6, 8, 3, (0, 8)))

    def held_chosen(x):
        return np.asarray(route(jnp.asarray(x), 6, 8, 3)[1])[:, :8]
    base, a, flips = held_chosen(logits), 0.02, 0
    for _ in range(5):
        moved = held_chosen(logits + rng.uniform(-a, a, logits.shape)
                            .astype(np.float32))
        changed = (moved != base).any(-1)
        flips += int(changed.sum())
        assert (margin[changed] < 2 * a).all()
    assert flips > 0
