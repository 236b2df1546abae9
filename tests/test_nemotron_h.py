"""``model = "nemotron_h"``: the chunked Mamba-2 scan against the per-token
recurrence, the expert layer's two forms against a loop over experts, the
model and ``Trainer``'s path against the plain reference
(``benchmarks/reference/nemotron_h.py``, which imports nothing from the
program), the shares tied to the uncut layers, and the decoder builder's
``olmo_hybrid`` against the steps built by hand."""

import dataclasses
import importlib
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.drivers.lm_epoch import draw_sequences, write_epoch  # noqa: E402
from benchmarks.reference import nemotron_h as R  # noqa: E402
from tdfo_tpu.core.config import LM_FAMILIES, LmSpec, read_configs  # noqa: E402
from tdfo_tpu.models import nemotron_h as M  # noqa: E402
from tdfo_tpu.models import olmo_hybrid  # noqa: E402
from tdfo_tpu.ops import moe  # noqa: E402
from tdfo_tpu.ops.ssd import chunk_ssd  # noqa: E402

LM = dict(vocab_size=50, hidden_size=32, hybrid_override_pattern="ME*",
          mamba_num_heads=8, mamba_head_dim=4, ssm_state_size=6, n_groups=4,
          num_attention_heads=8, num_key_value_heads=2, head_dim=8,
          n_routed_experts=16, num_experts_per_tok=3, moe_latent_size=16,
          moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
          routed_scaling_factor=2.5, rms_norm_eps=1e-5, mamba_heads_held=4,
          attention_heads_held=2, experts_held=4, first_expert_held=4)


def model_cfg(**over) -> M.LmConfig:
    return M.LmConfig(**{**LM, **over})


def reference_model(cfg: M.LmConfig, **over) -> dict:
    return dict(pattern=cfg.hybrid_override_pattern,
                mamba_heads=cfg.mamba_heads, mamba_groups=cfg.mamba_groups,
                mamba_head_dim=cfg.mamba_head_dim,
                ssm_state_size=cfg.ssm_state_size,
                attention_heads=cfg.attention_heads,
                key_value_heads=cfg.key_value_heads, head_dim=cfg.head_dim,
                n_routed_experts=cfg.n_routed_experts, experts=cfg.experts,
                first_expert_held=cfg.first_expert_held,
                num_experts_per_tok=cfg.num_experts_per_tok,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                rms_norm_eps=cfg.rms_norm_eps, token_block=16, query_block=24,
                **over)


def documents(lo, hi) -> dict:
    return {"documents": {"distribution": "log_uniform", "min": lo, "max": hi}}


def packed(rng, b, t, vocab=50, p_start=0.05):
    token = rng.integers(0, vocab, (b, t)).astype(np.int32)
    segment = np.cumsum(rng.random((b, t)) < p_start, axis=1).astype(np.int32)
    return token, segment


def seeded(cfg: M.LmConfig, seed: int = 0) -> dict:
    """Seeded weights at which every mechanism matters: projections large
    enough that the router's scores spread, a selection bias, a convolution
    bias and a skip ``D`` that are not their initial constants."""
    params = M.init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)

    def leaf(path, v):
        name = path[-1].key
        if name in ("router_bias", "conv_bias", "D"):
            return v + 0.3 * rng.normal(size=v.shape).astype(np.float32)
        return v * 5.0 if v.ndim >= 2 else v

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture
def small_chunks(monkeypatch):
    """The scan's chunk below the tests' sequence lengths, so that they
    cross chunks (the program's constant is the published 128)."""
    monkeypatch.setattr(M, "chunk_ssd",
                        lambda *a, **k: chunk_ssd(*a, chunk=16, **k))


# ---------------------------------------------------------------- the scan


@pytest.mark.parametrize("t,chunk,p_start", [
    (150, 64, 0.03),    # document starts inside chunks, ragged last chunk
    (128, 64, 0.0),     # one document, whole chunks
    (40, 64, 0.1),      # shorter than a chunk
    (100, 16, 0.2),     # several starts a chunk
])
def test_chunked_ssd_is_the_recurrence(t, chunk, p_start):
    rng = np.random.default_rng(t)
    b, h, p, g, n = 2, 4, 5, 2, 3
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    x, dt = f(b, t, h, p), np.log1p(np.exp(f(b, t, h)))
    a = -np.exp(f(h))
    b_mat, c_mat = f(b, t, g, n), f(b, t, g, n)
    starts = rng.random((b, t)) < p_start
    starts[:, 0] = True
    if p_start:
        starts[0, chunk // 2 if chunk < t else t // 2] = True  # inside a chunk
    starts = jnp.asarray(starts)
    of_head = lambda v: jnp.repeat(v, h // g, axis=2)

    def recurrence(x, dt, a, b_mat, c_mat):
        return jax.vmap(lambda *v: R.ssm(*v, block=32, bf16_state=False),
                        in_axes=(0, 0, None, 0, 0, 0))(
            x, dt, a, of_head(b_mat), of_head(c_mat), starts)

    def chunked(x, dt, a, b_mat, c_mat):
        return chunk_ssd(x, dt, a, b_mat, c_mat, starts, chunk=chunk)

    args = tuple(map(jnp.asarray, (x, dt, a, b_mat, c_mat)))
    with jax.default_matmul_precision("highest"):
        want, got = jax.jit(recurrence)(*args), jax.jit(chunked)(*args)
        np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
        weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(want.shape)
        grads = lambda fn: jax.jit(jax.grad(
            lambda *v: (fn(*v) * weight).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
        for name, u, v in zip("x dt a B C".split(), grads(chunked),
                              grads(recurrence)):
            np.testing.assert_allclose(u, v, atol=5e-5 * float(jnp.abs(v).max()),
                                       err_msg=name)


# ------------------------------------------------------------ expert layer


def routed_by_hand(x, weights, w1, w2):
    out = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        out = out + weights[:, e:e + 1] * (
            jnp.square(jax.nn.relu(x @ w1[e])) @ w2[e])
    return out


def expert_inputs(t=96, e=4, d=16, f=24, seed=0, p=0.2):
    rng = np.random.default_rng(seed)
    g = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    chosen = jnp.asarray(rng.random((t, e)) < p)
    weights = jnp.where(chosen, jnp.abs(g(t, e)) + 0.1, 0.0)
    return g(t, d), weights, chosen, 0.3 * g(e, d, f), 0.3 * g(e, f, d)


@pytest.mark.parametrize("rows,p", [
    (128, 0.2),         # the pairs fit the rows: sorted, ragged products
    (128, 0.02),        # hardly a pair; an expert with none
    (32, 0.2),          # more pairs than rows: the whole call falls back
    (32, 0.02),         # the same rows, few pairs: sorted
    (1000, 0.2),        # rows beyond T E = 384: as many as there can be
])
def test_held_experts_is_a_loop_over_experts(rows, p):
    x, weights, chosen, w1, w2 = expert_inputs(p=p)
    run = lambda x, weights, w1, w2: moe.held_experts(
        x, weights, chosen, w1, w2, rows=rows)
    with jax.default_matmul_precision("highest"):
        out, pairs, computed, load_max, fell_back = jax.jit(run)(
            x, weights, w1, w2)
        want = routed_by_hand(x, weights, w1, w2)
        np.testing.assert_allclose(out, want, atol=1e-4)
        assert int(pairs) == int(computed) == int(chosen.sum())
        assert int(load_max) == int(chosen.sum(axis=0).max())
        assert (int(pairs) > 32) == (p == 0.2) and int(pairs) <= 128
        assert int(fell_back) == (rows == 32 and p == 0.2)
        got = jax.grad(lambda *v: (run(*v)[0] ** 2).sum(), argnums=(0, 1, 2, 3))(
            x, weights, w1, w2)
        ref = jax.grad(lambda *v: (routed_by_hand(*v) ** 2).sum(),
                       argnums=(0, 1, 2, 3))(x, weights, w1, w2)
        for name, u, v in zip("x weights w1 w2".split(), got, ref):
            # the weights' gradient is wanted where an expert is chosen
            v = jnp.where(chosen, v, 0.0) if name == "weights" else v
            u = jnp.where(chosen, u, 0.0) if name == "weights" else u
            np.testing.assert_allclose(u, v, atol=2e-4 * float(jnp.abs(v).max()),
                                       err_msg=name)


@pytest.mark.parametrize("favoured", [(5, 0, 1), (5, 6, 7)])
def test_no_token_is_dropped_when_every_token_chooses_the_same_experts(favoured):
    """Every token on ONE held expert (5.3 times what uniform routing sends
    to the four held together; the sorted form), or on THREE (1.3 times the
    sorted form's static rows: the whole layer falls back to the dense
    form): every pair is computed."""
    cfg = model_cfg(hybrid_override_pattern="E")
    p = seeded(cfg)["layer_0"]["part"]
    # the bias puts these three first for every token; held are experts 4-7
    p = dict(p, router_bias=jnp.zeros(16).at[jnp.array(favoured)].set(10.0))
    held = sum(4 <= e < 8 for e in favoured)
    u = jnp.asarray(np.random.default_rng(0).normal(size=(2, 512, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, counters = jax.jit(lambda u: M.expert_layer(p, u, cfg))(u)
        assert {k: int(v) for k, v in counters.items()} == {
            "moe_pairs": held * 1024, "moe_pairs_computed": held * 1024,
            "moe_load_max": 1024, "moe_layers_dense": int(held == 3)}
        assert (held * 1024 > 2304) == (held == 3)      # the static rows
        m = reference_model(cfg)
        want = jax.jit(jax.vmap(lambda us: R.experts(p, us, m, None)))(u)
        np.testing.assert_allclose(out, want, atol=1e-4 * float(jnp.abs(want).max()))
        dropped = jax.jit(jax.vmap(lambda us: R.experts(p, us, m, "drop_tokens")))(u)
        assert float(jnp.abs(dropped - want).max()) > 1e-2 * float(jnp.abs(want).max())


def test_router_takes_the_lowest_indices_among_equal_scores():
    u = jnp.zeros((3, 8))           # every score 0.5: all equal
    weights, chosen = moe.route(u, jnp.zeros((8, 6)), jnp.zeros(6), top_k=2,
                                scale=5.0)
    assert chosen.tolist() == [[True, True, False, False, False, False]] * 3
    np.testing.assert_allclose(weights[:, :2], 2.5)
    bias = jnp.array([0.0, 0.0, 0.3, 0.0, 0.3, 0.0])
    weights, chosen = moe.route(u, jnp.zeros((8, 6)), bias, top_k=3, scale=1.0,
                                normalise=False)
    assert chosen[0].tolist() == [True, False, True, False, True, False]
    np.testing.assert_allclose(weights[0, jnp.array([0, 2, 4])], 0.5)  # s, not s + b


# ------------------------------------------------ the model and the reference


@pytest.mark.parametrize("pattern", ["ME*", "MEMEMEM*EME"])
def test_model_matches_the_reference_loss_and_gradients(pattern, small_chunks):
    cfg = model_cfg(hybrid_override_pattern=pattern)
    params = seeded(cfg, seed=len(pattern))
    rng = np.random.default_rng(1)
    token, segment = packed(rng, 2, 40)
    table = jnp.asarray(0.5 * rng.normal(size=(50, 32)), jnp.float32)
    m = reference_model(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, counters), got = jax.jit(jax.value_and_grad(
            lambda p, tb: M.forward_loss(p, tb[token], token, segment, cfg),
            argnums=(0, 1), has_aux=True))(params, table)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p, tb: R.forward_loss(p, tb, token, segment, m),
            argnums=(0, 1)))(params, table)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert int(counters["moe_pairs"]) == int(counters["moe_pairs_computed"]) > 0
    flat = lambda tree: dict(R._flat(tree[0]), table=tree[1])
    got, want = flat(got), flat(want)
    assert set(got) == set(want)
    for name, w in want.items():
        np.testing.assert_allclose(got[name], w, atol=2e-4 * float(jnp.abs(w).max())
                                   + 1e-9, err_msg=name)
    assert not np.asarray(got["layer_1/part/router_bias"]).any()


# ------------------------------------------------------- the shares add up


def test_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """16 experts over 4 shares: every share's routed part (in the latent,
    projected up) plus the shared expert ONCE is the uncut reference layer."""
    whole = model_cfg(hybrid_override_pattern="E", experts_held=0,
                      first_expert_held=0)
    p = seeded(whole)["layer_0"]["part"]
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, 40, 32)), jnp.float32)
    shared = lambda us: jnp.square(jax.nn.relu(us @ p["shared_in"])) @ p["shared_out"]
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda us: R.experts(p, us, reference_model(whole), None))(u)
        total, pairs = shared(u), 0
        for share in range(4):
            cfg = model_cfg(hybrid_override_pattern="E", experts_held=4,
                            first_expert_held=4 * share)
            part = dict(p, w1=p["w1"][4 * share:4 * share + 4],
                        w2=p["w2"][4 * share:4 * share + 4])
            out, counters = M.expert_layer(part, u, cfg)
            total = total + out - shared(u)
            pairs += int(counters["moe_pairs"])
        np.testing.assert_allclose(total, want, atol=1e-4 * float(jnp.abs(want).max()))
    assert pairs == 2 * 40 * 3          # every token's k experts lie on some share


def _mamba_share(p, cfg_whole, share, shares):
    h, hp, n = cfg_whole.mamba_num_heads, cfg_whole.mamba_head_dim, cfg_whole.ssm_state_size
    g, inner = cfg_whole.n_groups, h * hp
    hs, gs = h // shares, g // shares
    x = np.arange(inner).reshape(shares, -1)[share]
    grp = lambda base: base + np.arange(g * n).reshape(shares, -1)[share]
    heads = np.arange(h).reshape(shares, -1)[share]
    conv = np.concatenate([x, grp(inner), grp(inner + g * n)])
    cols = np.concatenate([x, inner + conv, 2 * inner + 2 * g * n + heads])
    assert len(heads) == hs and len(grp(0)) == gs * n
    return {"in_proj": p["in_proj"][:, cols], "conv_w": p["conv_w"][:, conv],
            "conv_bias": p["conv_bias"][conv], "A_log": p["A_log"][heads],
            "dt_bias": p["dt_bias"][heads], "D": p["D"][heads],
            "gate_norm": p["gate_norm"][x], "out_proj": p["out_proj"][x]}


def _attention_share(p, cfg_whole, share, shares):
    dh, hq, hkv = cfg_whole.head_dim, cfg_whole.num_attention_heads, cfg_whole.num_key_value_heads
    q = np.arange(hq * dh).reshape(shares, -1)[share]
    kv_head = share * hkv // shares          # two shares on each key/value head
    kv = kv_head * dh + np.arange(dh)
    return {"wq": p["wq"][:, q], "wk": p["wk"][:, kv], "wv": p["wv"][:, kv],
            "wo": p["wo"][q]}


@pytest.mark.parametrize("kind", ["M", "*"])
def test_four_head_shares_add_up_to_the_uncut_layer(kind, small_chunks):
    """The 4 head shares of a mixer (of the attention layer two on each
    key/value head) sum to the whole layer, which is the reference's."""
    whole = model_cfg(hybrid_override_pattern=kind, mamba_heads_held=0,
                      attention_heads_held=0)
    p = seeded(whole)["layer_0"]["part"]
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(2, 40, 32)), jnp.float32)
    segment = jnp.asarray(packed(rng, 2, 40)[1])
    share_cfg = model_cfg(hybrid_override_pattern=kind, mamba_heads_held=2,
                          attention_heads_held=2)
    assert share_cfg.key_value_heads == 1 and share_cfg.mamba_groups == 1
    ref = R.mamba2 if kind == "M" else R.attention
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(
            lambda us, ss: ref(p, us, ss, reference_model(whole), None)))(u, segment)
        mixer, cut = ((M.mamba2_mixer, _mamba_share) if kind == "M" else
                      (M.attention_mixer, _attention_share))
        held = jax.jit(lambda part: mixer(part, u, segment, share_cfg))
        total = sum(held(cut(p, whole, share, 4)) for share in range(4))
        np.testing.assert_allclose(total, want, atol=1e-4 * float(jnp.abs(want).max()))


# ---------------------------------------------------------- Trainer's path


def trainer_config(data_dir, **over):
    return read_configs(None, **{**dict(
        model="nemotron_h", data_dir=str(data_dir),
        checkpoint_dir=str(Path(data_dir) / "out"), max_len=48,
        per_device_train_batch_size=2, nonfinite_tolerance=0,
        model_parallel=True, learning_rate=1e-2, weight_decay=0.1,
        per_device_eval_batch_size=2, log_every_n_steps=2, mesh=dict(data=1),
        lm=dict(LM)), **over})


def test_trainer_path_matches_the_reference_over_three_steps(tmp_path):
    """One ``train_epoch`` of three donated steps on ``Trainer``'s own path
    against the reference's three steps from the same weights: losses, the
    first gradient, the parameters' change leaf by leaf; the selection bias
    stands still; the step's counters reach the epoch line."""
    from tdfo_tpu.train.trainer import Trainer

    write_epoch(tmp_path, *draw_sequences(0, 6, 48, 50, documents(4, 48)),
                files=2)
    trainer = Trainer(trainer_config(tmp_path), devices=jax.devices()[:1])
    bias = 0.2 * jnp.cos(jnp.arange(16.0))
    dense = trainer.state.dense_params
    dense = dict(dense, layer_1=dict(dense["layer_1"], part=dict(
        dense["layer_1"]["part"], router_bias=bias)))
    trainer.state = dataclasses.replace(
        trainer.state, dense_params=jax.device_put(
            dense, jax.tree.leaves(trainer.state.dense_params)[0].sharding))
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
    # the eval pass takes the loss of a step that also returns counters
    for part in (tmp_path / "parquet_lm").glob("train_part_*.parquet"):
        shutil.copy(part, part.with_name(part.name.replace("train", "eval")))
    assert np.isfinite(trainer.evaluate(0)["eval_loss"])
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
    assert got["dense:layer_1/part/router_bias"] == 0.0
    assert [c["moe_pairs"] for c in counted] == want["moe_pairs"]
    assert [c["moe_load_max"] for c in counted] == want["moe_load_max"]
    line = [json.loads(x) for x in
            (tmp_path / "out" / "metrics.jsonl").read_text().splitlines()
            if "train_loss_epoch" in x][-1]
    assert line["lm_tokens"] == 3 * 2 * 48 and line["steps"] == 3
    assert line["moe_pairs"] == line["moe_pairs_computed"] == sum(want["moe_pairs"])
    assert line["moe_load_max"] == sum(want["moe_load_max"])
    assert line["moe_layers_dense"] == 0      # every layer's pairs fit the rows


def test_config_holds_each_family_to_its_keys(tmp_path):
    """``core/config`` knows of a family its name and the keys it needs > 0;
    the keys it reads are its ``LmConfig``'s fields, each a key of the
    ``[lm]`` table, and what they must satisfy together is that class's to
    say: ``Trainer`` constructs it at build."""
    from tdfo_tpu.train.trainer import Trainer

    table = {f.name for f in dataclasses.fields(LmSpec)}
    for name, positive in LM_FAMILIES.items():
        reads = {f.name for f in dataclasses.fields(importlib.import_module(
            f"tdfo_tpu.models.{name}").LmConfig)}
        assert set(positive) <= reads <= table, name
    cfg = trainer_config(tmp_path)
    assert cfg.is_causal_lm and cfg.lm.hybrid_override_pattern == "ME*"
    assert not read_configs(None, model="twotower").is_causal_lm
    build = lambda c: Trainer(c, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="does not read .*intermediate_size"):
        build(trainer_config(tmp_path, lm=dict(LM, intermediate_size=48)))
    with pytest.raises(ValueError, match="does not read .*n_groups"):
        build(read_configs(None, model="olmo_hybrid", nonfinite_tolerance=0,
                           data_dir=str(tmp_path), lm=dict(
            vocab_size=50, hidden_size=32, intermediate_size=48,
            layer_types=["full_attention"], num_attention_heads=4,
            linear_key_head_dim=6, linear_value_head_dim=12, n_groups=2)))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        build(trainer_config(tmp_path, lm=dict(LM, hybrid_override_pattern="MXE")))
    with pytest.raises(ValueError, match="whole groups"):
        model_cfg(mamba_heads_held=3)
    with pytest.raises(ValueError, match="first_expert_held"):
        model_cfg(first_expert_held=14)
    with pytest.raises(ValueError, match="attention_heads_held"):
        model_cfg(attention_heads_held=9)
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        model_cfg(num_experts_per_tok=17)
    with pytest.raises(ValueError, match="needs \\[lm\\] moe_latent_size"):
        trainer_config(tmp_path, lm={k: v for k, v in LM.items()
                                     if k != "moe_latent_size"})
    with pytest.raises(ValueError, match="nonfinite_tolerance = 0"):
        trainer_config(tmp_path, nonfinite_tolerance=3)


@pytest.mark.parametrize("key,value", [
    ("conv_kernel", 3), ("norm_topk_prob", False), ("routed_scaling_factor", 1.0),
    ("first_expert_held", 8), ("attention_heads_held", 4),
    ("mamba_heads_held", 8), ("experts_held", 8), ("rms_norm_eps", 1e-2),
])
def test_every_lm_key_of_the_family_moves_the_loss(key, value, small_chunks):
    rng = np.random.default_rng(4)
    token, segment = packed(rng, 2, 40)
    embedded = jnp.asarray(0.5 * rng.normal(size=(2, 40, 32)), jnp.float32)
    loss = lambda cfg: float(jax.jit(lambda p: M.forward_loss(
        p, embedded, token, segment, cfg)[0])(seeded(cfg)))
    assert loss(model_cfg()) != loss(model_cfg(**{key: value}))


def test_the_decoder_builder_gives_olmo_hybrid_the_steps_built_by_hand(tmp_path):
    """``Trainer._build_causal_lm`` serves two families; ``olmo_hybrid``'s
    three steps through it are, bit for bit, those of the step built the way
    ``_build_olmo_hybrid`` built it: the family's own names, AdamW with no
    mask, a forward that returns the loss alone."""
    from tdfo_tpu.train.sparse_step import make_sparse_train_step
    from tdfo_tpu.train.trainer import _LM_ADAM_B2, Trainer

    lm = dict(vocab_size=50, hidden_size=32, intermediate_size=48,
              layer_types=["linear_attention", "full_attention"],
              num_attention_heads=4, linear_key_head_dim=6,
              linear_value_head_dim=12)
    config = read_configs(None, model="olmo_hybrid", data_dir=str(tmp_path),
                          checkpoint_dir=str(tmp_path / "out"), max_len=48,
                          per_device_train_batch_size=2, nonfinite_tolerance=0,
                          model_parallel=True, learning_rate=1e-2,
                          weight_decay=0.1, mesh=dict(data=1), lm=lm)
    write_epoch(tmp_path, *draw_sequences(0, 6, 48, 50, documents(4, 48)),
                files=2)
    trainer = Trainer(config, devices=jax.devices()[:1])
    assert trainer._step_counters == ()
    model_cfg_ = olmo_hybrid.LmConfig(**{**lm, "layer_types": tuple(
        lm["layer_types"])})
    assert trainer.model_cfg == model_cfg_
    state0 = jax.tree.map(jnp.copy, trainer.state)
    tx = optax.adamw(1e-2, b2=_LM_ADAM_B2, weight_decay=0.1)
    assert jax.tree.structure(tx.init(state0.dense_params)) == \
        jax.tree.structure(state0.opt_state)
    by_hand = make_sparse_train_step(
        trainer.coll,
        lambda dense, embs, batch: olmo_hybrid.forward_loss(
            dense, embs["token"], batch["token"], batch["segment"], model_cfg_,
            dtype=jnp.float32),
        mode=config.lookup_mode)
    feed, losses = [], []

    class Keep:
        def __init__(self, inner):
            self.inner = inner

        def __call__(self, state, batch, *rest):
            feed.append(batch)
            out = self.inner(state, batch, *rest)
            losses.append(out[1])
            return out

    trainer.train_step = Keep(trainer.train_step)
    trainer.train_epoch(0)
    trainer.logger.close()
    state, want = state0, []
    for batch in feed:
        state, loss = by_hand(state, batch, trainer._dropout_rng)
        want.append(loss)
    assert [float(x) for x in losses] == [float(x) for x in want]
    got = jax.device_get((trainer.state.dense_params, trainer.state.opt_state,
                          trainer.state.tables))
    for (path, a), b in zip(
            jax.tree.leaves_with_path(got),
            jax.tree.leaves(jax.device_get(
                (state.dense_params, state.opt_state, state.tables)))):
        np.testing.assert_array_equal(a, b, err_msg=str(path))
