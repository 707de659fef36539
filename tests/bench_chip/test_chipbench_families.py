"""A configuration of a family beyond the dense ``lm`` enters the harness
by files alone: a tiny mixture-of-experts configuration on the program's
``olmoe-1b-7b`` reaches the program, the reference's sizes and the
comparison's record, one leaf per expert; files that misstate what runs
are refused."""
import copy
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_paths import BENCH  # noqa: F401

import chip_harness
import correctness
import token_generator
from chipbench_tiny import WIDTHS, tiny_cell

EXPERTS, TOP_K = 16, 2   # 16: a count the program's padding leaves as is
PUBLISHED = dict(hidden_size=2048, intermediate_size=1024,
                 num_attention_heads=16, num_key_value_heads=16, head_dim=128,
                 num_hidden_layers=16, vocab_size=50304, num_experts=64,
                 num_experts_per_tok=8)
MOE = {
    "name": "olmoe-tiny", "source": "https://arxiv.org/abs/2409.02060",
    "program_arch": "olmoe-1b-7b", "family": "moe",
    "architectures": ["OlmoeForCausalLM"],
    **WIDTHS, "num_experts": EXPERTS, "num_experts_per_tok": TOP_K,
    "norm_topk_prob": False, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": True, "activation_dtype": "bfloat16",
    "program_fields": {"intermediate_size": "d_expert",
                       "num_experts": "n_experts",
                       "num_experts_per_tok": "top_k",
                       "norm_topk_prob": "moe_renorm"},
    "reduced": sorted(PUBLISHED), "published": PUBLISHED,
    "why_reduced": {k: "cut to a size a CPU test run holds"
                    for k in PUBLISHED},
    "program_departures": {},
}
EXPERT_LEAVES = ("layers.moe.wi_gate", "layers.moe.wi_up", "layers.moe.wo")


def moe_shapes(a: dict) -> dict:
    """The program's parameter layout, from the published keys alone."""
    d, h, kv = (a["hidden_size"], a["num_attention_heads"],
                a["num_key_value_heads"])
    hd, f, e = a["head_dim"], a["intermediate_size"], a["num_experts"]
    n = a["num_hidden_layers"]
    return {"embed": {"table": (a["vocab_size"], d)},
            "final_norm": {"scale": (d,)},
            "layers": {"ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
                       "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                                "wv": (n, d, kv, hd), "wo": (n, h, hd, d),
                                "q_norm": (n, hd), "k_norm": (n, hd)},
                       "moe": {"router": (n, d, e), "wi_gate": (n, e, d, f),
                               "wi_up": (n, e, d, f), "wo": (n, e, f, d)}}}


def make_weights(s: dict, key):
    shapes = moe_shapes(s["arch"])
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda x:
                                    isinstance(x, tuple))
    return jax.tree.unflatten(tree, [
        jax.random.normal(jax.random.fold_in(key, i), shape) * 0.02
        for i, shape in enumerate(leaves)])


def train(s, opt, key, batches, *, norms, **_):
    """Stands in for a reference's training: the first weights' norms,
    through the ``norms`` the harness hands it."""
    first = jax.device_get(norms(make_weights(s, key)))
    return [1.0] * len(batches), first, first


STUB = types.SimpleNamespace(make_weights=make_weights, train=train,
                             EXPERT_LEAVES=EXPERT_LEAVES)


def moe_cell():
    return dataclasses.replace(tiny_cell("smollm-135m.train.1chip"),
                               config=copy.deepcopy(MOE), reference=STUB)


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """The test process keeps JAX's compile cache as it found it."""
    monkeypatch.setattr(chip_harness, "use_compile_cache", lambda: None)


def test_moe_sizes_reach_the_program():
    cfg = chip_harness.program_config(copy.deepcopy(MOE))
    assert (cfg.family, cfg.n_experts, cfg.n_experts_padded, cfg.top_k,
            cfg.moe_renorm, cfg.d_expert) == ("moe", EXPERTS, EXPERTS, TOP_K,
                                              False, 128)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim_, cfg.n_layers,
            cfg.vocab) == (64, 4, 2, 16, 2, 512)


def _untie(c):
    c["tie_word_embeddings"] = False


def _top_k_not_the_programs(c):
    # stated but not listed as reduced: the program runs its own top-8
    c["reduced"].remove("num_experts_per_tok")
    del c["published"]["num_experts_per_tok"]
    del c["why_reduced"]["num_experts_per_tok"]


def _cut_reaches_no_field(c):
    del c["program_fields"]["num_experts"]


def _field_not_in_program(c):
    c["program_fields"]["num_experts"] = "experts"


@pytest.mark.parametrize("spoil, why", [
    pytest.param(_untie, "runs tie_word_embeddings=True", id="untied"),
    pytest.param(_top_k_not_the_programs, "program sizes", id="top_k"),
    pytest.param(_cut_reaches_no_field,
                 r"cuts of scale \['num_experts'\] reach no field",
                 id="cut_reaches_no_field"),
    pytest.param(_field_not_in_program, "names no ArchConfig field",
                 id="no_such_field")])
def test_a_file_that_misstates_the_program_is_refused(spoil, why):
    config = copy.deepcopy(MOE)
    spoil(config)
    with pytest.raises(ValueError, match=why):
        chip_harness.program_config(config)


def test_new_family_reference_and_record_per_expert():
    """A reference that reads only ``sizes["arch"]`` passes the layout
    check, and the program's record and the reference's hold one norm
    per (layer, expert)."""
    cell = moe_cell()
    prog = chip_harness.build_program(cell)
    assert prog.sizes["arch"]["num_experts"] == EXPERTS
    assert prog.sizes["arch"]["num_experts_per_tok"] == TOP_K
    assert prog.sizes["published"]["num_experts"] == 64
    assert not {"program_fields", "reduced", "published"} \
        & set(prog.sizes["arch"])
    pool = token_generator.pool_for(cell.traffic, prog.cfg.vocab, 2 ** 33)
    _, record, _ = chip_harness.first_steps(prog, cell, pool,
                                            chip_harness.weight_key(2 ** 33))
    n = WIDTHS["num_hidden_layers"]
    for part in ("grad", "update"):
        for leaf in EXPERT_LEAVES:
            got = {k for k in record[part] if k.startswith(leaf + "[")}
            assert got == {f"{leaf}[{i},{e}]" for i in range(n)
                           for e in range(EXPERTS)}
        assert {k for k in record[part] if k.startswith(
            "layers.moe.router")} == {f"layers.moe.router[{i}]"
                                      for i in range(n)}
    assert all(np.isfinite(v) and v > 0 for v in record["grad"].values())
    ref = chip_harness.reference_record(cell, prog, pool,
                                        chip_harness.weight_key(2 ** 33))
    assert set(ref["grad"]) == set(ref["update"]) == set(record["grad"])


def test_expert_leaves_split_the_layer_norm():
    x = jax.random.normal(jax.random.key(0), (2, 3, 4, 5))
    tree = {"embed": {"table": x[0, 0]}, "layers": {"moe": {"wo": x}}}
    plain = correctness.slice_norms(tree)
    split = correctness.slice_norms(tree, experts=("layers.moe.wo",))
    assert plain["layers.moe.wo"].shape == (2,)
    assert split["layers.moe.wo"].shape == (2, 3)
    np.testing.assert_allclose(jnp.sqrt((split["layers.moe.wo"] ** 2).sum(1)),
                               plain["layers.moe.wo"], rtol=1e-6)
    flat = correctness.flatten(split)
    assert set(flat) == {"embed.table"} | {f"layers.moe.wo[{i},{e}]"
                                           for i in range(2) for e in range(3)}
    assert set(correctness.flatten(plain)) == {"embed.table",
                                               "layers.moe.wo[0]",
                                               "layers.moe.wo[1]"}
