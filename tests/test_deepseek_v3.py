"""``model = "deepseek_v3"``: the causal attention with values narrower than
queries and keys against a dense masked softmax, the rotary pair layout
against the published ``view / transpose``, the model and ``Trainer``'s
path against the plain reference (``benchmarks/reference/deepseek_v3.py``,
which imports nothing from the program), the expert shares tied to the
uncut layer, the admitted pairs counted, and the configuration's refusals."""

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.drivers.lm_epoch import draw_sequences, write_epoch  # noqa: E402
from benchmarks.reference import deepseek_v3 as R  # noqa: E402
from tdfo_tpu.core.config import read_configs  # noqa: E402
from tdfo_tpu.models import deepseek_v3 as M  # noqa: E402
from tdfo_tpu.models import olmo_hybrid  # noqa: E402

LM = dict(vocab_size=50, hidden_size=64, num_hidden_layers=3,
          num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
          first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=2,
          moe_intermediate_size=24, n_shared_experts=2,
          routed_scaling_factor=2.446, rope_theta=1e4, rms_norm_eps=1e-5,
          experts_held=4, first_expert_held=2)


def model_cfg(**over) -> M.LmConfig:
    return M.LmConfig(**{**LM, **over})


def reference_model(cfg: M.LmConfig, **over) -> dict:
    return dict(layers=cfg.num_hidden_layers, heads=cfg.num_attention_heads,
                kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim,
                first_k_dense_replace=cfg.first_k_dense_replace,
                experts=cfg.experts, first_expert_held=cfg.first_expert_held,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
                query_block=24, **over)


def packed(rng, b=2, t=64, docs=3):
    token = rng.integers(0, 50, (b, t)).astype(np.int32)
    cuts = np.sort(rng.choice(np.arange(3, t - 3), docs - 1, replace=False))
    segment = np.zeros((b, t), np.int32)
    for c in cuts:
        segment[:, c:] += 1
    return token, segment


def seeded(cfg: M.LmConfig, seed: int = 0) -> dict:
    """Weights large enough that the router's scores spread and every
    mechanism matters, and a selection bias that moves the choice."""
    params = M.init_params(jax.random.key(seed), cfg)
    params = jax.tree.map(lambda v: v * 5.0 if v.ndim >= 2 else v, params)
    for i in range(cfg.first_k_dense_replace, cfg.num_hidden_layers):
        params[f"layer_{i}"]["mlp"]["router_bias"] = 0.05 * jax.random.normal(
            jax.random.key(seed + i), (cfg.n_routed_experts,))
    return params


@pytest.fixture(scope="module")
def small_tiles():
    """Query tiles of 32 below the tests' 64 positions: documents lie across
    tiles and keys are read in two blocks."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(M, "causal_document_attention", functools.partial(
            olmo_hybrid.causal_document_attention, query_block=32))
        yield


# ------------------------------------------------------------- attention


@pytest.mark.parametrize("t,block", [(64, 16), (70, 32)])
def test_attention_with_narrower_values_is_a_dense_masked_softmax(t, block):
    """q, k of 24 channels against v of 16: values and the three gradients
    of the blockwise attention equal one masked softmax at scale
    1/sqrt(24); documents across query tiles, a ragged last tile (70)."""
    rng = np.random.default_rng(t)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v, w = f(2, t, 3, 24), f(2, t, 3, 24), f(2, t, 3, 16), f(2, t, 3, 16)
    segment = jnp.asarray(np.cumsum(rng.random((2, t)) < 0.08, 1), jnp.int32)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = (j <= i) & (np.asarray(segment)[:, :, None]
                       == np.asarray(segment)[:, None, :])

    def dense(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(24)
        p = jax.nn.softmax(jnp.where(mask[:, None], logits, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    run = lambda fn: jax.jit(lambda q, k, v: (fn(q, k, v), jax.grad(
        lambda *a: (fn(*a) * w).sum(), (0, 1, 2))(q, k, v)))(q, k, v)
    got = run(lambda *a: olmo_hybrid.causal_document_attention(
        *a, segment, query_block=block))
    want = run(dense)
    assert got[0].shape == (2, t, 3, 16)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_rope_is_the_published_pair_layout_and_sees_only_differences():
    """``rope_pairs`` is the reference's literal ``view(d/2, 2).transpose``
    then rotate-half; and a document's MLA output does not depend on where
    it lies in the packed sequence."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 20, 3, 8)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 500, (2, 20)), jnp.int32)
    got, want, plain = jax.jit(lambda x, pos: (
        M.rope_pairs(x, pos, 1e4),
        jax.vmap(lambda a, p: R.apply_rotary(a, p, 1e4))(x, pos),
        jax.vmap(lambda a, p: R.apply_rotary(a, p, 1e4, "rope_rotate_half"))(
            x, pos)))(x, pos)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(plain - want).max()) > 0.1

    # one document first in one sequence and behind another of 13 tokens in
    # the other
    cfg = model_cfg()
    p = seeded(cfg)["layer_0"]["attn"]
    doc = rng.normal(size=(24, 64)).astype(np.float32)
    other = rng.normal(size=(13, 64)).astype(np.float32)
    x = jnp.asarray(np.stack([np.concatenate([doc, other]),
                              np.concatenate([other, doc])]))
    segment = jnp.asarray(np.stack([np.repeat([0, 1], [24, 13]),
                                    np.repeat([0, 1], [13, 24])]), jnp.int32)
    pos, _ = M.document_blocks(segment, 1)
    out = jax.jit(M.mla_mixer, static_argnums=4)(p, x, segment, pos, cfg)
    np.testing.assert_allclose(out[1, 13:], out[0, :24], atol=2e-4, rtol=1e-4)


def test_attn_pairs_is_the_mask_counted():
    rng = np.random.default_rng(6)
    segment = jnp.asarray(np.cumsum(rng.random((3, 90)) < 0.05, 1), jnp.int32)
    assert int(M.attention_pairs(segment)) == int(R.admitted_pairs(segment))
    assert int(M.attention_pairs(jnp.zeros((1, 8), jnp.int32))) == 36


# ------------------------------------------------ the model and the reference


def program_loss(cfg, token, segment):
    def loss(p, table):
        return M.forward_loss(p, table[token], jnp.asarray(token),
                              jnp.asarray(segment), cfg)
    return loss


def test_model_matches_the_reference_loss_and_gradients(small_tiles):
    cfg = model_cfg()
    params = seeded(cfg, seed=1)
    rng = np.random.default_rng(1)
    token, segment = packed(rng)
    table = jnp.asarray(0.5 * rng.normal(size=(50, 64)), jnp.float32)
    (loss, counters), got = jax.jit(jax.value_and_grad(
        program_loss(cfg, token, segment), argnums=(0, 1), has_aux=True))(
        params, table)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, tb: R.forward_loss(p, tb, jnp.asarray(token),
                                     jnp.asarray(segment), reference_model(cfg)),
        argnums=(0, 1)))(params, table)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert int(counters["moe_pairs"]) == int(counters["moe_pairs_computed"]) > 0
    assert int(counters["attn_pairs"]) == 3 * int(R.admitted_pairs(segment))
    flat = lambda tree: dict(R._flat(tree[0]), table=tree[1])
    got, want = flat(got), flat(want)
    assert set(got) == set(want)
    for name, w in want.items():
        np.testing.assert_allclose(got[name], w, atol=2e-5 * float(jnp.abs(w).max())
                                   + 1e-9, err_msg=name)


def test_expert_shares_with_what_every_chip_computes_once_add_up():
    """8 experts over 2 shares of 4: every share's expert layer less its
    input, attention and shared experts, plus those ONCE, is the uncut
    reference layer."""
    whole = model_cfg(num_hidden_layers=2, experts_held=0, first_expert_held=0)
    p = seeded(whole)["layer_1"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    segment = jnp.asarray(packed(rng, t=32)[1])
    pos, _ = M.document_blocks(segment, 1)
    want = jax.jit(jax.vmap(lambda xs, ss: R.block(
        p, xs, ss, False, reference_model(whole), None)))(x, segment)

    @jax.jit
    def shares(p):
        u = olmo_hybrid.rms_norm(x, p["attn_norm"], whole.rms_norm_eps)
        h = x + M.mla_mixer(p["attn"], u, segment, pos, whole)
        shared = M.swiglu_mlp(p["mlp"]["shared"], olmo_hybrid.rms_norm(
            h, p["mlp_norm"], whole.rms_norm_eps))
        total, pairs = h + shared, 0
        for share in range(2):
            cfg = model_cfg(num_hidden_layers=2, experts_held=4,
                            first_expert_held=4 * share)
            held = slice(4 * share, 4 * share + 4)
            part = dict(p, mlp=dict(p["mlp"], w1=p["mlp"]["w1"][held],
                                    w2=p["mlp"]["w2"][held]))
            out, counters = M._block(part, x, segment, pos, False, cfg)
            total = total + out - h - shared
            pairs += counters["moe_pairs"]
        return total, pairs

    total, pairs = shares(p)
    np.testing.assert_allclose(total, want, atol=1e-4 * float(jnp.abs(want).max()))
    assert int(pairs) == 2 * 32 * 2    # every token's k experts on a share


# ---------------------------------------------------------- Trainer's path


def trainer_config(data_dir, **over):
    return read_configs(None, **{**dict(
        model="deepseek_v3", data_dir=str(data_dir),
        checkpoint_dir=str(Path(data_dir) / "out"), max_len=48,
        per_device_train_batch_size=2, nonfinite_tolerance=0,
        model_parallel=True, learning_rate=1e-2, weight_decay=0.1,
        per_device_eval_batch_size=2, log_every_n_steps=2, mesh=dict(data=1),
        lm=dict(LM)), **over})


def test_trainer_path_matches_the_reference_over_three_steps(tmp_path):
    """One ``train_epoch`` of three donated steps on ``Trainer``'s own path
    against the reference's three steps from the same weights: losses, the
    first gradient and the parameters' change leaf by leaf (the selection
    bias a buffer that stands still), the expert pairs; the counters reach
    the epoch line."""
    from tdfo_tpu.train.trainer import Trainer

    write_epoch(tmp_path, *draw_sequences(0, 6, 48, 50, {"documents": {
        "distribution": "log_uniform", "min": 4, "max": 48}}), files=2)
    # the dense layer and one expert layer: every kind of layer once
    trainer = Trainer(trainer_config(tmp_path, lm=dict(LM, num_hidden_layers=2)),
                      devices=jax.devices()[:1])
    dense0 = jax.device_get(trainer.state.dense_params)
    table0 = np.asarray(trainer.state.tables["token_embedding"])
    feed, losses, counted, mu1 = [], [], [], None

    class Keep:
        def __init__(self, inner):
            self.inner = inner

        def __call__(self, state, batch, *rest):
            nonlocal mu1
            feed.append(jax.device_get(batch))
            out = self.inner(state, batch, *rest)
            losses.append(float(out[1][0]))
            counted.append(jax.device_get(out[1][1]))
            if mu1 is None:
                mu1 = jax.device_get(next(
                    s.mu for s in out[0].opt_state if hasattr(s, "mu")))
            return out

    trainer.train_step = Keep(trainer.train_step)
    trainer.train_epoch(0)
    trainer.logger.close()
    assert len(feed) == 3 and int(trainer.state.step) == 3
    optim = {"dense": dict(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1),
             "sparse": dict(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8)}
    want = R.run_steps(reference_model(trainer.model_cfg), optim, dense0,
                       table0, feed)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    flat = lambda tree: {f"dense:{k}": v for k, v in R._flat(tree).items()}
    for leaf, mu in flat(mu1).items():
        assert abs(np.linalg.norm(mu) / 0.1 - want["grad_norm"][leaf]) \
            <= 1e-4 * want["grad_norm"][leaf] + 1e-7, leaf
    after = flat(jax.device_get(trainer.state.dense_params))
    got = {k: float(np.linalg.norm(np.asarray(v) - flat(dense0)[k]))
           for k, v in after.items()}
    got["table:token"] = float(np.linalg.norm(
        np.asarray(trainer.state.tables["token_embedding"]) - table0))
    for leaf, w in want["update_norm"].items():
        assert abs(got[leaf] - w) <= 2e-3 * w + 1e-7, (leaf, got[leaf], w)
    assert got["dense:layer_1/mlp/router_bias"] == 0.0
    assert [int(c["moe_pairs"]) for c in counted] == want["moe_pairs"]
    pairs = [2 * int(R.admitted_pairs(b["segment"])) for b in feed]
    assert [int(c["attn_pairs"]) for c in counted] == pairs
    line = [json.loads(x) for x in
            (tmp_path / "out" / "metrics.jsonl").read_text().splitlines()
            if "train_loss_epoch" in x][-1]
    assert line["attn_pairs"] == sum(pairs)
    assert line["moe_pairs"] == line["moe_pairs_computed"] == sum(want["moe_pairs"])


def test_config_holds_the_family_to_its_keys(tmp_path):
    from tdfo_tpu.train.trainer import Trainer

    with pytest.raises(ValueError, match="q_lora_rank = 1536: the query's "
                                         "low-rank path is not implemented"):
        trainer_config(tmp_path, lm=dict(LM, q_lora_rank=1536))
    with pytest.raises(ValueError, match="needs \\[lm\\] kv_lora_rank"):
        trainer_config(tmp_path, lm={k: v for k, v in LM.items()
                                     if k != "kv_lora_rank"})
    with pytest.raises(ValueError, match="does not read .*block_length"):
        Trainer(trainer_config(tmp_path, lm=dict(LM, block_length=4)),
                devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        model_cfg(first_k_dense_replace=4)
    with pytest.raises(ValueError, match="first_expert_held"):
        model_cfg(first_expert_held=6)
