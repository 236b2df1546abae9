"""``model = "sdar_moe"``: block diffusion's attention against a dense
masked softmax, its kernel's forward once a layer in the chip's step, the
noise from (seed, step), rotary positions, the model and
``Trainer``'s path against the plain reference
(``benchmarks/reference/sdar_moe.py``), the expert shares tied to the uncut
layer, and the shared pieces held to their forms before this family came:
the causal attention and the sigmoid / relu-squared expert layer."""

import collections
import dataclasses
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
from benchmarks.reference import sdar_moe as R  # noqa: E402
from tdfo_tpu.core.config import read_configs  # noqa: E402
from tdfo_tpu.models import olmo_hybrid  # noqa: E402
from tdfo_tpu.models import sdar_moe as M  # noqa: E402
from tdfo_tpu.ops import moe  # noqa: E402
from tdfo_tpu.ops import pallas_kernels as PK  # noqa: E402
from tests.test_olmo_hybrid import equations  # noqa: E402

LM = dict(vocab_size=50, hidden_size=64, num_hidden_layers=2,
          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
          rope_theta=1e4, block_length=4, noise_eps=1e-3,
          experts_held=4, first_expert_held=2)


def model_cfg(**over) -> M.LmConfig:
    return M.LmConfig(**{**LM, **over})


def reference_model(cfg: M.LmConfig, **over) -> dict:
    return dict(layers=cfg.num_hidden_layers,
                attention_heads=cfg.num_attention_heads,
                key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                num_experts=cfg.num_experts, experts=cfg.experts,
                first_expert_held=cfg.first_expert_held,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob, rms_norm_eps=cfg.rms_norm_eps,
                rope_theta=cfg.rope_theta, block_length=cfg.block_length,
                mask_token_id=cfg.mask_token_id, noise_eps=cfg.noise_eps,
                query_block=24, **over)


def packed(rng, b=2, t=64, docs=3):
    """``b`` sequences of ``t`` tokens, ``docs`` documents each at cuts
    that do not fall on a block's edge."""
    token = rng.integers(0, 49, (b, t)).astype(np.int32)
    cuts = np.sort(rng.choice(np.arange(5, t - 5), docs - 1, replace=False))
    segment = np.zeros((b, t), np.int32)
    for c in cuts:
        segment[:, c:] += 1
    return token, segment


def seeded(cfg: M.LmConfig, seed: int = 0) -> dict:
    """Weights large enough that the router's scores spread and every
    mechanism matters."""
    params = M.init_params(jax.random.key(seed), cfg)
    return jax.tree.map(lambda v: v * 5.0 if v.ndim >= 2 else v, params)


@pytest.fixture
def small_blocks(monkeypatch):
    """Query blocks of 32 below the tests' 128 positions, so that the noisy
    and clean halves take tiles of their own and blocks straddle tiles."""
    monkeypatch.setattr(M, "causal_document_attention", functools.partial(
        olmo_hybrid.causal_document_attention, query_block=32))


def program_loss(cfg, token, segment, key):
    def loss(p, table):
        b = M.prepare_batch({"token": jnp.asarray(token),
                             "segment": jnp.asarray(segment)}, key, cfg)
        return M.forward_loss(p, table[b["token"]], b["token"], b["segment"],
                              cfg, weight=b["weight"])
    return loss


# ------------------------------------------------------------- attention


def dense_attention(q, k, v, visible):
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(visible[:, None], logits, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("kind,block,length,block_length", [
    ("diffusion", 32, 64, 4), ("diffusion", 1024, 64, 4),
    ("diffusion", 16, 256, 4), ("diffusion", 32, 512, 67),
    ("diffusion", 16, 64, 1), ("causal", 16, 64, 1),
    ("kernel", (64, 64), 64, 4), ("kernel", (16, 32), 256, 4),
    ("kernel", (32, 16), 512, 67), ("kernel", (16, 16), 64, 1),
    ("kernel_kept", (16, 32), 64, 4)])
def test_the_attention_is_a_dense_masked_softmax(kind, block, length,
                                                 block_length):
    """The blockwise attention, values and gradients, against one masked
    softmax whose mask is the rule written out (``reference.visible``):
    one query tile, two, tiles whose fixed key windows are clipped at
    either end of a half (256 positions in tiles of 16), blocks that
    reach past a whole tile (67), blocks of one token.  ``kernel``: the
    chip's Pallas kernel (``ops/pallas_kernels.bd_attention``, forward and
    both backward kernels) under the interpreter, tiles ``(block_q,
    block_k)``: one query tile a copy, many, key tiles wider and narrower
    than the query tiles, documents across tile ends and tiles whose first
    document began tiles earlier (256 positions in three documents).
    ``kernel_kept``: the same kernel under the layers' checkpoint policy
    (``sdar_moe.LAYER_POLICY``), which keeps its output and log-sum-exp
    for the backward pass: values and gradients are the unwrapped
    kernel's, bit for bit, and the dense softmax's as every case's."""
    rng = np.random.default_rng(np.prod(block) + length)
    _, segment = packed(rng, t=length)
    b, h, hkv, dh = 2, 4, 2, 8
    n = 2 * length if kind != "causal" else length
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k, v = f(b, n, h, dh), f(b, n, hkv, dh), f(b, n, hkv, dh)
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    if kind != "causal":
        seg = np.concatenate([segment, segment], 1)
        blk = np.asarray(M.document_blocks(segment, block_length)[1])
        noisy = np.arange(n) < length
        rule = jax.vmap(lambda bl: R.visible(i, j, noisy[i], noisy[j], bl[i],
                                             bl[j], None))(np.tile(blk, 2))
        kw = dict(block_of=jnp.asarray(blk), block_length=block_length)
    else:
        seg, rule = segment, np.broadcast_to(j <= i, (b, n, n))
        kw = {}
    mask = np.asarray(rule) & (seg[:, None, :] == seg[:, :, None])
    blockwise = lambda q, k, v: olmo_hybrid.causal_document_attention(
        q, k, v, jnp.asarray(segment), query_block=block, **kw)
    if kind.startswith("kernel"):
        blockwise = lambda q, k, v: PK.bd_attention(
            q, k, v, jnp.asarray(segment), block_of=kw["block_of"],
            block_length=block_length, block_q=block[0], block_k=block[1],
            interpret=True)
    plain = lambda q, k, v: dense_attention(
        q, jnp.repeat(k, h // hkv, 2), jnp.repeat(v, h // hkv, 2), mask)
    w = f(b, n, h, dh)
    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *x: (fn(*x) * w).sum(), (0, 1, 2))(*a)))(q, k, v)
    if kind == "kernel_kept":
        # the forward and the backward pass as programs of their own: in
        # one program XLA may fuse the backward's row sum ``delta`` with
        # the cotangent's producer and order it otherwise (1 ulp)
        def apart(fn):
            o, back = jax.jit(lambda *a: jax.vjp(fn, *a))(q, k, v)
            return o, jax.jit(lambda back, w: back(w))(back, w)

        kept = jax.checkpoint(blockwise, policy=M.LAYER_POLICY)
        for x, y in zip(jax.tree.leaves(apart(kept)),
                        jax.tree.leaves(apart(blockwise))):
            np.testing.assert_array_equal(x, y)
        blockwise = kept
    (oa, ga), (ob, gb) = both(blockwise), both(plain)
    np.testing.assert_allclose(oa, ob, atol=2e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=2e-5)


def test_the_backward_pass_runs_the_forward_kernel_once_a_layer():
    """On the chip each layer keeps the attention kernel's output and
    log-sum-exp across its rematerialisation (``sdar_moe.LAYER_POLICY``):
    the value and gradient of the backbone run ``bd_attn_fwd`` once a
    layer, not a second time in the rematerialised forward, beside one
    ``bd_attn_bwd_dq`` and one ``bd_attn_bwd_dkv``.  Traced with
    ``platform="tpu"``; nothing is compiled or run."""
    cfg = model_cfg()
    rng = np.random.default_rng(4)
    _, segment = packed(rng, b=1, t=64)
    segment = jnp.asarray(segment)
    _, block_of = M.document_blocks(segment, cfg.block_length)
    x = jnp.asarray(rng.normal(size=(1, 128, cfg.hidden_size)), jnp.bfloat16)

    def loss(p, x):
        y, _ = M.backbone(p, x, segment, block_of, cfg, platform="tpu")
        return y.astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(
        M.init_params(jax.random.key(4), cfg), x)
    kernels = collections.Counter(
        eqn.params["name"] for eqn in equations(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call")
    layers = cfg.num_hidden_layers
    assert kernels == {"bd_attn_fwd": layers, "bd_attn_bwd_dq": layers,
                       "bd_attn_bwd_dkv": layers}, kernels


def host_tile_pairs(segment, block_length, block_q, block_k):
    """The kernel's (query tile, key tile) pairs of one packed row, written
    out on the host: per query its key intervals in each copy, per tile and
    copy the hull of the rows that see a key there."""
    length = len(segment)
    start, end = np.zeros(length, int), np.zeros(length, int)
    for s in range(length):
        start[s] = s if s == 0 or segment[s] != segment[s - 1] else start[s - 1]
    for s in reversed(range(length)):
        end[s] = s + 1 if s == length - 1 or segment[s] != segment[s + 1] \
            else end[s + 1]
    blk = start + (np.arange(length) - start) // block_length * block_length
    blk_end = np.minimum(blk + block_length, end)
    pairs = set()
    for qt in range(2 * length // block_q):
        rows = np.arange(qt * block_q, (qt + 1) * block_q)
        if rows[0] < length:     # noisy: own block noisy, earlier blocks clean
            spans = [(blk[rows], blk_end[rows]),
                     (length + start[rows], length + blk[rows])]
        else:
            rows = rows - length
            spans = [(length + start[rows], length + blk_end[rows])]
        for lo, hi in spans:
            live = hi > lo
            if live.any():
                pairs |= {(qt, kt) for kt in range(
                    lo[live].min() // block_k,
                    -(-hi[live].max() // block_k))}
    return pairs


def test_the_tile_schedule_covers_every_admitted_pair(monkeypatch):
    """Over random packings (documents of 1 token up, blocks of 1 to 67,
    tiles wider and narrower than each other): every (query, key) pair the
    rule admits lies in a listed tile pair, the query spans ARE the rule,
    the pairs are the host's recount in both orders, and the step's
    ``bd_attn_tiles`` is the layers times that recount."""
    rng = np.random.default_rng(11)
    schedule = jax.jit(lambda seg, blk, bl, bq, bk: (
        PK.bd_query_spans(seg, blk, bl),
        *PK.bd_tile_pairs(PK.bd_query_spans(seg, blk, bl), bq, bk, bl)),
        static_argnums=(2, 3, 4))
    for length, bl, bq, bk in [(64, 4, 8, 8), (64, 1, 16, 8), (128, 3, 8, 32),
                               (128, 67, 32, 16), (64, 4, 64, 64)]:
        cuts = np.sort(rng.choice(np.arange(1, length), rng.integers(0, 8),
                                  replace=False))
        segment = np.zeros((2, length), np.int32)
        for c in cuts:
            segment[0, c:] += 1
        segment[1] = np.repeat(np.arange(length // 2), 2)   # 2-token documents
        blk = np.asarray(M.document_blocks(segment, bl)[1])
        spans, qt, kt, n = (np.asarray(a) for a in schedule(
            jnp.asarray(segment), jnp.asarray(blk), bl, bq, bk))
        key_order = [np.asarray(a) for a in PK._bd_by_key_tile(
            jnp.asarray(qt), jnp.asarray(kt), jnp.asarray(n))]
        n2 = 2 * length
        i, j = np.arange(n2)[:, None], np.arange(n2)[None, :]
        noisy = np.arange(n2) < length
        for row in range(2):
            seg, bb = np.tile(segment[row], 2), np.tile(blk[row], 2)
            rule = np.asarray(R.visible(i, j, noisy[i], noisy[j], bb[i], bb[j],
                                        None)) & (seg[i] == seg[j])
            lo = np.where(noisy[j], spans[row, :, 0:1], spans[row, :, 2:3])
            hi = np.where(noisy[j], spans[row, :, 1:2], spans[row, :, 3:4])
            np.testing.assert_array_equal((j >= lo) & (j < hi), rule)
            listed = set(zip(qt[row, :n[row]].tolist(), kt[row, :n[row]].tolist()))
            assert len(listed) == n[row]
            assert listed == host_tile_pairs(segment[row], bl, bq, bk)
            qi, kj = np.nonzero(rule)
            assert set(zip((qi // bq).tolist(), (kj // bk).tolist())) <= listed
            # the steps past the count repeat the last pair
            assert (qt[row, n[row]:] == qt[row, n[row] - 1]).all()
            by_key = list(zip(key_order[0][row, :n[row]].tolist(),
                              key_order[1][row, :n[row]].tolist()))
            assert by_key == sorted((k, q) for q, k in listed)
    # the step's counter, at tiles of 16 over 64 positions
    monkeypatch.setattr(PK, "BD_BLOCK_Q", 16)
    monkeypatch.setattr(PK, "BD_BLOCK_K", 16)
    cfg = model_cfg()
    token, segment = packed(np.random.default_rng(12), t=64)
    _, counters = jax.jit(program_loss(cfg, token, segment, R.step_key(0, 1)))(
        seeded(cfg), jnp.zeros((50, 64), jnp.float32))
    want = sum(len(host_tile_pairs(row, cfg.block_length, 16, 16))
               for row in segment)
    assert int(counters["bd_attn_tiles"]) == cfg.num_hidden_layers * want


def test_rope_sees_only_position_differences_within_a_document():
    """A document's attention output does not depend on where it lies in
    the packed sequence: the same document behind another of 13 tokens."""
    cfg = model_cfg()
    p = seeded(cfg)["layer_0"]["attn"]
    rng = np.random.default_rng(5)
    doc = rng.normal(size=(1, 24, 64)).astype(np.float32)
    pre = rng.normal(size=(1, 13, 64)).astype(np.float32)

    def run(x, segment):   # both copies alike: the stream [x | x]
        _, blk = M.document_blocks(jnp.asarray(segment), cfg.block_length)
        u = jnp.concatenate([x, x], axis=1)
        return M.attention_mixer(p, jnp.asarray(u), jnp.asarray(segment), blk, cfg)

    run = jax.jit(run)
    alone = run(doc, np.zeros((1, 24), np.int32))
    behind = run(np.concatenate([pre, doc], 1),
                 np.repeat([[0, 1]], [13, 24], axis=1).astype(np.int32))
    for half in range(2):   # the noisy copy and the clean one
        np.testing.assert_allclose(behind[:, 13 + 37 * half:37 * (half + 1)],
                                   alone[:, 24 * half:24 * (half + 1)],
                                   atol=2e-4, rtol=1e-4)


# ----------------------------------------------------------------- noise


def test_the_noise_is_a_function_of_seed_and_step_and_of_its_definition():
    _, segment = packed(np.random.default_rng(0), b=4, t=512, docs=5)
    segment = jnp.asarray(segment)
    noise = jax.jit(lambda key: M.block_noise(key, segment, 4, 1e-3))
    masked, t = noise(R.step_key(7, 2))
    again, t2 = noise(R.step_key(7, 2))
    other, _ = noise(R.step_key(7, 3))
    assert (np.asarray(masked) == np.asarray(again)).all()
    assert (np.asarray(t) == np.asarray(t2)).all()
    assert (np.asarray(masked) != np.asarray(other)).any()
    t, masked = np.asarray(t), np.asarray(masked)
    within, blk = (np.asarray(a) for a in M.document_blocks(segment, 4))
    assert ((t >= 1e-3) & (t <= 1.0)).all()
    for row in range(4):           # one level a block; blocks from each start
        key = segment[row] * 10_000 + blk[row]
        for g in np.unique(key):
            assert len(np.unique(t[row][key == g])) == 1
            assert (within[row][key == g] // 4 == blk[row][key == g]).all()
    assert within[0, 0] == 0 and (np.diff(within[0])[np.diff(segment[0]) == 0] == 1).all()
    # tokens are masked at their block's rate
    assert abs(masked.mean() - t.mean()) < 0.05
    # the reference's noise, drawn from its definition with the same key
    want_masked, want_t = R.noise(R.step_key(7, 2), segment, 4, 1e-3)
    np.testing.assert_array_equal(masked, want_masked)
    np.testing.assert_array_equal(t, want_t)


# ------------------------------------------------ the model and the reference


def test_model_matches_the_reference_loss_and_gradients(small_blocks):
    cfg = model_cfg()
    params = seeded(cfg, seed=1)
    rng = np.random.default_rng(1)
    token, segment = packed(rng)
    table = jnp.asarray(0.5 * rng.normal(size=(50, 64)), jnp.float32)
    key = R.step_key(3, 1)
    m = reference_model(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, counters), got = jax.jit(jax.value_and_grad(
            program_loss(cfg, token, segment, key), argnums=(0, 1),
            has_aux=True))(params, table)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p, tb: R.forward_loss(p, tb, jnp.asarray(token),
                                         jnp.asarray(segment), key, m),
            argnums=(0, 1)))(params, table)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert int(counters["moe_pairs"]) == int(counters["moe_pairs_computed"]) > 0
    assert int(counters["bd_masked_tokens"]) == int(R.noisy_batch(
        jnp.asarray(token), jnp.asarray(segment), key, m)[2].sum()) > 0
    flat = lambda tree: dict(R._flat(tree[0]), table=tree[1])
    got, want = flat(got), flat(want)
    assert set(got) == set(want)
    for name, w in want.items():
        np.testing.assert_allclose(got[name], w, atol=2e-5 * float(jnp.abs(w).max())
                                   + 1e-9, err_msg=name)


def test_expert_shares_with_attention_once_add_up_to_the_uncut_layer():
    """8 experts over 2 shares of 4: every share's layer less its input and
    attention, plus the attention ONCE, is the uncut reference layer."""
    whole = model_cfg(num_hidden_layers=1, experts_held=0, first_expert_held=0)
    p = seeded(whole)["layer_0"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 64, 64)), jnp.float32)
    segment = jnp.asarray(packed(rng, t=32)[1])
    _, blk = M.document_blocks(segment, 4)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda xs, ss: R.block(
            p, xs, ss, reference_model(whole), None)))(x, segment)
        u = olmo_hybrid.rms_norm(x, p["attn_norm"], whole.rms_norm_eps)
        h = x + jax.jit(M.attention_mixer, static_argnums=4)(
            p["attn"], u, segment, blk, whole)
        total, pairs = h, 0
        for share in range(2):
            cfg = model_cfg(num_hidden_layers=1, experts_held=4,
                            first_expert_held=4 * share)
            part = dict(p, moe=dict(p["moe"],
                                    w1=p["moe"]["w1"][4 * share:4 * share + 4],
                                    w2=p["moe"]["w2"][4 * share:4 * share + 4]))
            out, counters = jax.jit(lambda part, cfg=cfg: M._block(
                part, x, segment, blk, cfg))(part)
            total = total + out - h
            pairs += int(counters["moe_pairs"])
        np.testing.assert_allclose(total, want, atol=1e-4 * float(jnp.abs(want).max()))
    assert pairs == 2 * 64 * 2         # every position's k experts on a share


# ---------------------------------------------------------- Trainer's path


def trainer_config(data_dir, **over):
    return read_configs(None, **{**dict(
        model="sdar_moe", data_dir=str(data_dir),
        checkpoint_dir=str(Path(data_dir) / "out"), max_len=48,
        per_device_train_batch_size=2, nonfinite_tolerance=0,
        model_parallel=True, learning_rate=1e-2, weight_decay=0.1,
        per_device_eval_batch_size=2, log_every_n_steps=2, mesh=dict(data=1),
        lm=dict(LM)), **over})


def test_trainer_path_matches_the_reference_over_three_steps(tmp_path):
    """One ``train_epoch`` of three donated steps on ``Trainer``'s own path,
    each drawing its noise inside the step from (seed, step), against the
    reference's three steps from the same weights and the same keys:
    losses, the first gradient, the parameters' change leaf by leaf, the
    masked positions and the expert pairs; the counters reach the epoch
    line."""
    from tdfo_tpu.train.trainer import Trainer

    write_epoch(tmp_path, *draw_sequences(0, 6, 48, 49, {"documents": {
        "distribution": "log_uniform", "min": 4, "max": 48}}), files=2)
    config = trainer_config(tmp_path, lm=dict(LM, num_hidden_layers=1))
    trainer = Trainer(config, devices=jax.devices()[:1])
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
    for part in (tmp_path / "parquet_lm").glob("train_part_*.parquet"):
        part.with_name(part.name.replace("train", "eval")).write_bytes(
            part.read_bytes())
    assert np.isfinite(trainer.evaluate(0)["eval_loss"])
    trainer.logger.close()
    assert len(feed) == 3 and int(trainer.state.step) == 3
    optim = {"dense": dict(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1),
             "sparse": dict(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8)}
    want = R.run_steps(reference_model(trainer.model_cfg), optim, dense0,
                       table0, feed, seed=config.seed)
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
    assert [int(c["bd_masked_tokens"]) for c in counted] == want["bd_masked"]
    assert [int(c["moe_pairs"]) for c in counted] == want["moe_pairs"]
    line = [json.loads(x) for x in
            (tmp_path / "out" / "metrics.jsonl").read_text().splitlines()
            if "train_loss_epoch" in x][-1]
    assert line["bd_masked_tokens"] == sum(want["bd_masked"])
    assert line["moe_pairs"] == line["moe_pairs_computed"] == sum(want["moe_pairs"])


@pytest.mark.parametrize("key,value", [
    ("block_length", 2), ("rope_theta", 10.0), ("vocab_size", 40),
    ("noise_eps", 0.5), ("first_expert_held", 4),
])
def test_every_lm_key_of_the_family_moves_the_loss(key, value):
    rng = np.random.default_rng(4)
    token, segment = packed(rng, t=32)
    table = jnp.asarray(0.5 * rng.normal(size=(50, 64)), jnp.float32)
    loss = lambda cfg: float(jax.jit(program_loss(
        cfg, token, segment, R.step_key(0, 1)))(seeded(cfg), table)[0])
    one = dict(num_hidden_layers=1)
    assert loss(model_cfg(**one)) != loss(model_cfg(**one, **{key: value}))


def test_config_holds_the_family_to_its_keys(tmp_path):
    from tdfo_tpu.train.trainer import Trainer

    with pytest.raises(ValueError, match="needs \\[lm\\] block_length"):
        trainer_config(tmp_path, lm={k: v for k, v in LM.items()
                                     if k != "block_length"})
    with pytest.raises(ValueError, match="does not read .*hybrid_override_pattern"):
        Trainer(trainer_config(tmp_path, lm=dict(
            LM, hybrid_override_pattern="E")), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="first_expert_held"):
        model_cfg(first_expert_held=6)


def test_the_dense_form_one_expert_at_a_time_is_the_batched_one(monkeypatch):
    """Above ``DENSE_BYTES`` the dense form takes the experts one at a time
    (the cell's 16 x 16,384 x 1,536 products at once made its step 1.7 GiB
    of code): the same values and gradients as all experts at once."""
    rng = np.random.default_rng(9)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x, w1, w2 = f(48, 16), 0.3 * f(4, 16, 2 * 24), 0.3 * f(4, 24, 16)
    chosen = jnp.asarray(rng.random((48, 4)) < 0.6)
    weights = jnp.where(chosen, jnp.abs(f(48, 4)) + 0.1, 0.0)
    run = lambda *a: jax.jit(lambda x, w, w1, w2: (lambda out: (out[0], jax.grad(
        lambda *b: (moe.held_experts(b[0], b[1], chosen, b[2], b[3], rows=16,
                                     act=moe.swiglu)[0] ** 2).sum(),
        (0, 1, 2, 3))(x, w, w1, w2)))(moe.held_experts(
            x, w, chosen, w1, w2, rows=16, act=moe.swiglu)))(*a)
    with jax.default_matmul_precision("highest"):
        batched = run(x, weights, w1, w2)
        monkeypatch.setattr(moe, "DENSE_BYTES", 0)
        looped = run(x, weights, w1, w2)
    for a, b in zip(jax.tree.leaves(looped), jax.tree.leaves(batched)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()))


# ------------------------------- the shared pieces, held to their old forms


def _causal_before(q, k, v, segment, *, query_block):
    """``olmo_hybrid.causal_document_attention`` as it was before block
    diffusion (PR 37), kept here as the fixture's author."""
    b, t, h, dh = q.shape
    if k.shape[2] != h:
        k, v = (jnp.repeat(a, h // k.shape[2], axis=2) for a in (k, v))
    scale, block, pos, low = 1.0 / np.sqrt(dh), min(query_block, t), jnp.arange(t), -1e30

    def rows(q_b, k_b, v_b, seg_q, seg_k, pos_q, pos_k):
        n = k_b.shape[1] // block

        def keys(carry, xs):
            o, m, l = carry
            k_i, v_i, seg_i, pos_i = xs
            logits = jnp.einsum("bqhd,bkhd->bhqk", q_b, k_i,
                                preferred_element_type=jnp.float32) * scale
            ok = ((pos_i[None, :] <= pos_q[:, None])[None]
                  & (seg_i[:, None, :] == seg_q[:, :, None]))[:, None]
            logits = jnp.where(ok, logits, low)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.where(ok, jnp.exp(logits - m_new[..., None]), 0.0)
            shrink = jnp.exp(m - m_new)
            l = l * shrink + p.sum(axis=-1)
            o = o * shrink[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_i.dtype), v_i,
                preferred_element_type=jnp.float32)
            return (o, m_new, l), None

        split = lambda a: jnp.moveaxis(
            a.reshape(a.shape[0], n, block, *a.shape[2:]), 1, 0)
        o0 = jnp.swapaxes(q_b, 1, 2).astype(jnp.float32) * 0.0
        (o, _, l), _ = jax.lax.scan(
            jax.checkpoint(keys), (o0, o0[..., 0] + low, o0[..., 0]),
            (split(k_b), split(v_b), split(seg_k), pos_k.reshape(n, block)))
        return jnp.swapaxes(o / l[..., None], 1, 2).astype(v_b.dtype)

    out = []
    for a in range(0, t, block):
        e = min(a + block, t)
        pad = -e % block
        widen = lambda x, fill=0: jnp.pad(
            x[:, :e], [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2),
            constant_values=fill)
        out.append(rows(q[:, a:e], widen(k), widen(v), segment[:, a:e],
                        widen(segment, -1), pos[a:e],
                        jnp.pad(pos[:e], (0, pad), constant_values=t)))
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]


def _route_before(u, w_router, bias, *, top_k, scale):
    """``ops/moe.route`` as it was (sigmoid, normalised), verbatim."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(u.astype(f32), w_router.astype(f32),
                               precision=moe.ROUTER_PRECISION))
    pick = jax.lax.stop_gradient(s + bias.astype(f32))
    top, at = jax.lax.top_k(pick, top_k)
    iota = jax.lax.broadcasted_iota(jnp.int32, pick.shape, pick.ndim - 1)
    chosen = (pick > top[..., -1:]) | ((pick == top[..., -1:])
                                       & (iota <= at[..., -1:]))
    picked = jnp.where(chosen, s, 0.0)
    picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return scale * picked, chosen


def _held_before(x, weights, chosen, w1, w2, *, rows):
    """``ops/moe.held_experts`` with ``relu(.)^2`` written in, verbatim."""
    f32 = jnp.float32
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    t, e = chosen.shape
    rows = min(rows, t * e)
    load = chosen.sum(axis=0)
    pairs = load.sum()

    def dense():
        h = relu2(jnp.einsum("td,edf->etf", x, w1.astype(x.dtype)))
        h = h * weights.T.astype(h.dtype)[..., None]
        return jnp.einsum("etf,efd->td", h, w2.astype(x.dtype),
                          preferred_element_type=f32).astype(x.dtype), pairs

    def sorted_():
        order = jnp.argsort(~chosen.T.reshape(-1), stable=True)[:rows]
        expert, token = order // t, order % t
        live = jnp.arange(rows) < load.sum()
        slot = jnp.where(chosen, (jnp.cumsum(load) - load)[None, :]
                         + jnp.cumsum(chosen, axis=0) - 1, -1)
        xs = moe._dispatch(x, token, slot, live)
        h = relu2(jax.lax.ragged_dot(xs, w1.astype(x.dtype), load))
        y = jax.lax.ragged_dot(h, w2.astype(x.dtype), load,
                               preferred_element_type=f32)
        w_rows = moe._rows_of(weights, token * e + expert, slot)
        y = (y * jnp.where(live, w_rows, 0.0)[:, None]).astype(x.dtype)
        return moe._combine(y, token, slot, live), live.sum()

    out, computed = jax.lax.cond(pairs <= rows, sorted_, dense)
    return out, pairs, computed


@pytest.mark.parametrize("t,block,kv", [(64, 16, 3), (64, 64, 1), (70, 32, 3)])
def test_the_causal_attention_is_its_old_form_bit_for_bit(t, block, kv):
    rng = np.random.default_rng(t + block)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(2, t, 3, 8), f(2, t, kv, 8), f(2, t, kv, 8)
    segment = jnp.asarray(np.cumsum(rng.random((2, t)) < 0.1, 1), jnp.int32)
    w = f(2, t, 3, 8)
    run = lambda fn: jax.jit(lambda q, k, v: (fn(q, k, v), jax.grad(
        lambda *a: (fn(*a) * w).sum(), (0, 1, 2))(q, k, v)))(q, k, v)
    new = run(lambda *a: olmo_hybrid.causal_document_attention(
        *a, segment, query_block=block))
    old = run(lambda *a: _causal_before(*a, segment, query_block=block))
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rows", [64, 16])     # sorted, and the dense form
def test_the_sigmoid_relu2_expert_layer_is_its_old_form_bit_for_bit(rows):
    rng = np.random.default_rng(rows)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    u, w_r, bias = f(48, 16), f(16, 12), 0.1 * f(12)
    w1, w2 = 0.3 * f(4, 16, 24), 0.3 * f(4, 24, 16)

    def layer(route, held):
        def run(u, w_r, w1, w2):
            weights, chosen = route(u, w_r, bias, top_k=3, scale=2.5)
            out = held(u, weights[:, 4:8], chosen[:, 4:8], w1, w2, rows=rows)
            return out[0], out[1], out[2]
        return jax.jit(lambda *a: (run(*a), jax.grad(
            lambda *b: (run(*b)[0] ** 2).sum(), (0, 1, 2, 3))(*a)))(u, w_r, w1, w2)

    new = layer(lambda *a, **k: moe.route(*a, **k), moe.held_experts)
    old = layer(_route_before, _held_before)
    assert int(new[0][1]) == int(old[0][1]) and (int(new[0][1]) > rows) == (rows == 16)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        np.testing.assert_array_equal(a, b)
