"""``model = "olmo_hybrid"``: the chunked gated delta rule against the
per-token recurrence, causal-within-document attention against a masked
softmax, the model on ``Trainer``'s path against the plain reference
(``benchmarks/reference/olmo_hybrid.py``, which imports nothing from the
program), and the head shares tied to the uncut layer."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.drivers.lm_epoch import draw_sequences, write_epoch  # noqa: E402
from benchmarks.reference import olmo_hybrid as R  # noqa: E402
from tdfo_tpu.core.config import read_configs  # noqa: E402
from tdfo_tpu.models import olmo_hybrid as M  # noqa: E402
from tdfo_tpu.ops.gated_delta import chunk_gated_delta_rule  # noqa: E402

LAYERS = ("linear_attention", "linear_attention", "linear_attention",
          "full_attention")
LM = dict(vocab_size=50, hidden_size=32, intermediate_size=48,
          layer_types=list(LAYERS), num_attention_heads=4,
          linear_key_head_dim=6, linear_value_head_dim=12)


def model_cfg(**over) -> M.LmConfig:
    return M.LmConfig(**{**LM, "layer_types": LAYERS, **over})


def reference_model(cfg: M.LmConfig, **over) -> dict:
    return dict(layer_types=list(cfg.layer_types), head_dim=cfg.head_dim,
                full_heads=cfg.full_heads, linear_heads=cfg.linear_heads,
                linear_key_head_dim=cfg.linear_key_head_dim,
                linear_value_head_dim=cfg.linear_value_head_dim,
                linear_allow_neg_eigval=cfg.linear_allow_neg_eigval,
                rms_norm_eps=cfg.rms_norm_eps, token_block=16, query_block=24,
                **over)


def documents(lo, hi) -> dict:
    """A traffic mix for the benchmark's generator (the one generator of
    packed sequences: the program reads them, it does not write them)."""
    return {"documents": {"distribution": "log_uniform", "min": lo, "max": hi}}


def packed(rng, b, t, vocab=50, p_start=0.05):
    token = rng.integers(0, vocab, (b, t)).astype(np.int32)
    segment = np.cumsum(rng.random((b, t)) < p_start, axis=1).astype(np.int32)
    return token, segment


@pytest.mark.parametrize("t,chunk,p_start", [
    (150, 64, 0.03),    # document starts inside chunks, ragged last chunk
    (128, 64, 0.0),     # one document, whole chunks
    (40, 64, 0.1),      # shorter than a chunk
    (100, 16, 0.2),     # several starts a chunk
])
def test_chunked_delta_rule_is_the_recurrence(t, chunk, p_start):
    rng = np.random.default_rng(t)
    b, h, dk, dv = 2, 2, 4, 8
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q = unit(f(b, t, h, dk)) / np.sqrt(dk)
    k = unit(f(b, t, h, dk) + 0.5)       # keys that share a mean direction
    v, g = f(b, t, h, dv), -0.3 * np.abs(f(b, t, h))
    beta = 2.0 / (1.0 + np.exp(-f(b, t, h)))
    starts = rng.random((b, t)) < p_start
    starts[:, 0] = True
    starts = jnp.asarray(starts)

    def recurrence(*a):
        return jax.vmap(lambda *x: R.delta_rule(*x, block=32, bf16_state=False)
                        )(*a, starts)

    def chunked(*a):
        return chunk_gated_delta_rule(*a, starts, chunk=chunk)

    args = (q, k, v, g, beta)
    w = f(b, t, h, dv)
    both = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a) * w).sum(), argnums=tuple(range(5)), has_aux=False))
    out = lambda fn: jax.jit(fn)(*args)
    np.testing.assert_allclose(out(chunked), out(recurrence), atol=2e-5)
    (la, ga), (lb, gb) = both(chunked)(*args), both(recurrence)(*args)
    assert abs(float(la) - float(lb)) < 1e-3
    for a, bb in zip(ga, gb):
        np.testing.assert_allclose(a, bb, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("t,block", [(70, 32), (64, 64), (50, 1024)])
def test_causal_document_attention_is_a_masked_softmax(t, block):
    rng = np.random.default_rng(block)
    b, h, dh = 2, 3, 8
    q, k, v = (rng.normal(size=(b, t, h, dh)).astype(np.float32)
               for _ in range(3))
    _, segment = packed(rng, b, t, p_start=0.1)

    def plain(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
        pos = np.arange(t)
        ok = ((pos[None, :] <= pos[:, None])[None]
              & (segment[:, None, :] == segment[:, :, None]))
        probs = jax.nn.softmax(jnp.where(ok[:, None], logits, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blockwise = lambda q, k, v: M.causal_document_attention(
        q, k, v, jnp.asarray(segment), query_block=block)
    w = rng.normal(size=(b, t, h, dh)).astype(np.float32)
    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *x: (fn(*x) * w).sum(), (0, 1, 2))(*a)))(q, k, v)
    (oa, ga), (ob, gb) = both(blockwise), both(plain)
    np.testing.assert_allclose(oa, ob, atol=1e-5)
    for a, bb in zip(ga, gb):
        np.testing.assert_allclose(a, bb, atol=1e-5)


def test_model_matches_the_reference_loss_and_gradients():
    cfg = model_cfg(layer_types=("linear_attention", "full_attention"))
    params = M.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    table = 0.1 * rng.normal(size=(50, 32)).astype(np.float32)
    token, segment = packed(rng, 2, 70)
    m = reference_model(cfg)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p, t: R.forward_loss(p, t, token, segment, m), (0, 1)))(params, table)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p, t: M.forward_loss(p, t[token], token, segment, cfg),
        (0, 1)))(params, table)
    assert abs(float(got) - float(want)) < 1e-5 and 3.5 < float(want) < 4.5
    for (path, a), b in zip(jax.tree.leaves_with_path(g_got),
                            jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=str(path))
    # the reference's faults move the loss: they are not no-ops
    for fault in ("bf16_state", "no_resets"):
        other = jax.jit(lambda p, t, fault=fault: R.forward_loss(
            p, t, token, segment, m, fault))(params, table)
        assert abs(float(other) - float(want)) > 1e-5, fault


def _slice_heads(p, kind, cfg, lo, hi):
    """The parameters of heads ``lo:hi`` of one mixer."""
    if kind == "full_attention":
        cols = slice(lo * cfg.head_dim, hi * cfg.head_dim)
        return {"wq": p["wq"][:, cols], "wk": p["wk"][:, cols],
                "wv": p["wv"][:, cols], "wo": p["wo"][cols],
                "q_norm": p["q_norm"][cols], "k_norm": p["k_norm"][cols]}
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    kc, vc = slice(lo * dk, hi * dk), slice(lo * dv, hi * dv)
    return {"wq": p["wq"][:, kc], "wk": p["wk"][:, kc], "wv": p["wv"][:, vc],
            "wg": p["wg"][:, vc], "wo": p["wo"][vc], "wa": p["wa"][:, lo:hi],
            "wb": p["wb"][:, lo:hi], "conv_q": p["conv_q"][:, kc],
            "conv_k": p["conv_k"][:, kc], "conv_v": p["conv_v"][:, vc],
            "A_log": p["A_log"][lo:hi], "dt_bias": p["dt_bias"][lo:hi],
            "o_norm": p["o_norm"]}


@pytest.mark.parametrize("kind", M.LAYER_KINDS)
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_two_head_shares_add_up_to_the_uncut_layer(kind, grad):
    """Each of two chips holds half the heads; their ``W_o`` outputs, before
    the block's norm, add up to what the uncut reference gives for the whole
    layer.  The delta-rule mixer is additive as it stands; the full layer's
    q/k RMSNorm needs the one scalar a token the two chips exchange (the
    mean square summed over the ``model`` axis), so it runs as a two-device
    ``shard_map``.  ``gradient``: so do the gradients with respect to the
    input and to each share's weights, which are the uncut layer's gradient
    cut the same way: the products' barriers are traced and transposed
    under the mesh axis."""
    whole = model_cfg(layer_types=(kind,))
    share = model_cfg(layer_types=(kind,), full_heads_held=2,
                      linear_heads_held=2)
    p = M.init_params(jax.random.key(1), whole)["layer_0"]["mixer"]
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 60, 32)).astype(np.float32)
    _, segment = packed(rng, 2, 60, p_start=0.08)
    w = rng.normal(size=(2, 60, 32)).astype(np.float32)
    m = reference_model(whole)
    # forward: the output; gradient: d sum(output * w) / d (weights, input)
    read = (lambda f: jax.grad(lambda hs, x: (f(hs, x) * w).sum(), (0, 1))
            ) if grad else (lambda f: f)
    want = jax.jit(read(lambda p, x: jax.vmap(
        lambda xs, ss: R.MIXERS[kind](p, xs, ss, m, None))(x, segment)))(p, x)
    cut = lambda p: [_slice_heads(p, kind, whole, 0, 2),
                     _slice_heads(p, kind, whole, 2, 4)]
    halves = cut(p)
    if kind == "linear_attention":
        got = jax.jit(read(lambda hs, x: sum(
            M.gated_delta_mixer(h, x, segment, share) for h in hs)))(halves, x)
    else:
        mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
        stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), *halves)

        def on_chip(h, x, segment):
            h = jax.tree.map(lambda a: a[0], h)
            out = M.full_attention_mixer(h, x, segment, share, axis_name="model")
            return jax.lax.psum(out, "model")

        mapped = jax.shard_map(on_chip, mesh=mesh, in_specs=(P("model"), P(), P()),
                               out_specs=P())
        got = jax.jit(read(lambda hs, x: mapped(hs, x, jnp.asarray(segment))))(
            stacked, x)
        if grad:
            got = ([jax.tree.map(lambda a: a[i], got[0]) for i in (0, 1)], got[1])
        # one chip alone norms over its own columns: a different layer
        alone = jax.jit(read(lambda hs, x: sum(
            M.full_attention_mixer(h, x, segment, share) for h in hs)))(halves, x)
        far = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                           alone, (cut(want[0]), want[1]) if grad else want)
        assert max(jax.tree.leaves(far)) > 1e-3
    if not grad:
        np.testing.assert_allclose(got, want, atol=2e-5)
        return
    g_halves, g_x = got
    np.testing.assert_allclose(g_x, want[1], atol=1e-4)
    shared = ("o_norm",)    # one leaf both shares read: their gradients add
    for name in halves[0]:
        if name in shared:
            np.testing.assert_allclose(g_halves[0][name] + g_halves[1][name],
                                       want[0][name], atol=1e-4, err_msg=name)
            continue
        for a, b in zip(g_halves, cut(want[0])):
            np.testing.assert_allclose(a[name], b[name], atol=1e-4, err_msg=name)


def trainer_config(data_dir, **over):
    return read_configs(None, **{**dict(
        model="olmo_hybrid", data_dir=str(data_dir),
        checkpoint_dir=str(Path(data_dir) / "out"), max_len=48,
        per_device_train_batch_size=2, nonfinite_tolerance=0,
        model_parallel=True, learning_rate=1e-2, weight_decay=0.1,
        log_every_n_steps=2, mesh=dict(data=1),
        lm=dict(LM, layer_types=["linear_attention", "full_attention"])),
        **over})


def test_trainer_path_matches_the_reference_over_three_steps(tmp_path):
    """One ``train_epoch`` of three donated steps on ``Trainer``'s own path
    (parquet stream, ``prefetch_to_mesh``, ``make_sparse_train_step``)
    against the reference's three steps from the same weights: losses, the
    first gradient (``mu_1 = (1 - b1) g``), the parameters' change leaf by
    leaf; and the epoch line's counters."""
    from tdfo_tpu.train.trainer import Trainer

    write_epoch(tmp_path, *draw_sequences(0, 6, 48, 50, documents(4, 48)),
                files=2)
    trainer = Trainer(trainer_config(tmp_path), devices=jax.devices()[:1])
    dense0 = jax.device_get(trainer.state.dense_params)
    table0 = np.asarray(trainer.state.tables["token_embedding"])
    feed, losses, mu1 = [], [], None

    class Keep:
        def __init__(self, inner):
            self.inner = inner

        def __call__(self, state, batch, *rest):
            nonlocal mu1
            feed.append(jax.device_get(batch))
            out = self.inner(state, batch, *rest)
            losses.append(float(out[1]))
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
    got = {k: float(np.linalg.norm(np.asarray(v) - flat(dense0)[k])) for k, v in
           flat(jax.device_get(trainer.state.dense_params)).items()}
    got["table:token"] = float(np.linalg.norm(
        np.asarray(trainer.state.tables["token_embedding"]) - table0))
    for leaf, w in want["update_norm"].items():
        assert abs(got[leaf] - w) <= 2e-3 * w + 1e-7, (leaf, got[leaf], w)
    line = [json.loads(x) for x in
            (tmp_path / "out" / "metrics.jsonl").read_text().splitlines()
            if "train_loss_epoch" in x][-1]
    assert line["lm_tokens"] == 3 * 2 * 48 and line["steps"] == 3
    assert line["lm_docs"] == sum(
        int((b["segment"][:, 1:] != b["segment"][:, :-1]).sum()) + 2 for b in feed)
    assert line["lm_label_tokens"] == line["lm_tokens"] - line["lm_docs"]


def test_config_holds_the_model_to_its_path(tmp_path):
    with pytest.raises(ValueError, match="nonfinite_tolerance = 0"):
        trainer_config(tmp_path, nonfinite_tolerance=3)
    with pytest.raises(ValueError, match="unknown lm config keys"):
        trainer_config(tmp_path, lm=dict(LM, chunk=64))
    # what the keys must satisfy together is the family's ``LmConfig``, which
    # ``Trainer`` constructs at build (``core/config`` does not import jax)
    from tdfo_tpu.train.trainer import Trainer
    with pytest.raises(ValueError, match="layer_types"):
        Trainer(trainer_config(tmp_path, lm=dict(LM, layer_types=["windowed"])),
                devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="full_heads_held"):
        model_cfg(full_heads_held=5)
    with pytest.raises(ValueError, match="olmo_hybrid"):
        read_configs(None, model="twotower", lm=dict(LM))
    with pytest.raises(ValueError, match="steps_per_execution"):
        trainer_config(tmp_path, steps_per_execution=4)
    cfg = trainer_config(tmp_path)
    assert cfg.lm.layer_types == LAYERS[2:]


def test_packed_documents_fill_the_sequence():
    token, segment = draw_sequences(3, 6, 512, 100, documents(16, 256))
    assert token.shape == segment.shape == (6, 512) and token.dtype == np.int32
    assert 0 <= token.min() and token.max() < 100
    steps = np.diff(segment, axis=1)
    assert (segment[:, 0] == 0).all() and set(np.unique(steps)) <= {0, 1}
    for row in segment:
        sizes = np.bincount(row)
        assert (sizes[:-1] >= 16).all() and sizes.max() <= 256


# ---- every large product reads operands that were made once (PR 35; PR 33
# ---- held the weight gradients apart from the optimizer's sweep)


def plain_products(monkeypatch):
    """The formulation before PRs 33 and 35, kept here: ``x @ w`` with the
    weight cast where it is used, nothing between an expression and the
    product that reads it, nor between a gradient and the optimizer."""
    monkeypatch.setattr(M, "cotangent_once", lambda y: y)
    monkeypatch.setattr(M, "made_once", lambda x: x)


def cotangents_once(cfg: M.LmConfig, b: int, t: int) -> list[tuple]:
    """Shapes of the product outputs whose cotangent is made once, one a
    site: the head's logits; in each layer ``gate``, ``up`` and ``down``; in
    a delta-rule layer ``wv`` and ``wg``.  The other products (``wq`` /
    ``wk`` / ``wo`` there, the full-attention layer's four, the tiny ``wa``
    / ``wb``) measured no faster for it in the step and have none."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    vw = cfg.linear_heads * cfg.linear_value_head_dim
    out = [(b, t, cfg.vocab_size)]
    for kind in cfg.layer_types:
        out += [(b, t, f), (b, t, f), (b, t, d)]
        if kind == "linear_attention":
            out += [(b, t, vw), (b, t, vw)]
    return sorted(out)


def operands_once(cfg: M.LmConfig, b: int, t: int) -> list[tuple]:
    """Shapes of the left operands that are expressions and are made once,
    one a site: in each layer ``h`` (into ``gate`` and ``up``); in a
    delta-rule layer the gated and normed output (into ``wo``)."""
    vw = cfg.linear_heads * cfg.linear_value_head_dim
    out = []
    for kind in cfg.layer_types:
        out += [(b, t, cfg.hidden_size)]
        if kind == "linear_attention":
            out += [(b, t, vw)]
    return sorted(out)


def barriers(jaxpr) -> list[list[tuple]]:
    """The operand shapes of every ``optimization_barrier`` in a jaxpr and
    in the jaxprs nested in it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "optimization_barrier":
            found.append([v.aval.shape for v in eqn.invars])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += barriers(sub)
    return found


@pytest.mark.parametrize("name,side", [("cotangent_once", "backward"),
                                       ("made_once", "forward")])
def test_an_identity_holds_its_barrier_on_one_side(name, side):
    """``cotangent_once`` is the mirror of ``made_once``: the one bars the
    cotangent and leaves the value alone, the other bars the value and
    leaves the cotangent alone; both are identities with the identity's
    gradient and keep nothing for the backward pass."""
    fn = getattr(M, name)
    x = jnp.arange(6.0).reshape(2, 3)
    value, back = jax.vjp(fn, x)
    np.testing.assert_array_equal(value, x)
    np.testing.assert_array_equal(back(2 * x)[0], 2 * x)
    forward = barriers(jax.make_jaxpr(fn)(x).jaxpr)
    backward = barriers(jax.make_jaxpr(back)(x).jaxpr)
    assert forward == ([[(2, 3)]] if side == "forward" else [])
    assert backward == ([[(2, 3)]] if side == "backward" else [])
    assert not jax.tree.leaves(back)      # nothing saved for the backward


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradients_are_those_of_the_plain_products(dtype, monkeypatch):
    cfg = model_cfg(layer_types=("linear_attention", "full_attention"))
    params = M.init_params(jax.random.key(2), cfg)
    rng = np.random.default_rng(2)
    token, segment = packed(rng, 2, 40)
    emb = 0.1 * rng.normal(size=(2, 40, 32)).astype(np.float32)

    def value_and_grads():
        return jax.jit(jax.value_and_grad(lambda p, e: M.forward_loss(
            p, e, token, segment, cfg, dtype=dtype), (0, 1)))(params, emb)

    loss, grads = value_and_grads()
    plain_products(monkeypatch)
    want_loss, want = value_and_grads()
    assert float(loss) == float(want_loss)
    for (path, a), b in zip(jax.tree.leaves_with_path(grads),
                            jax.tree.leaves(want)):
        assert a.dtype == jnp.float32
        np.testing.assert_array_equal(a, b, err_msg=str(path))


def test_three_trainer_steps_are_those_of_the_plain_products(
        tmp_path, monkeypatch):
    from tdfo_tpu.train.trainer import Trainer

    write_epoch(tmp_path, *draw_sequences(0, 6, 48, 50, documents(4, 48)),
                files=2)

    def three_steps():
        trainer = Trainer(trainer_config(tmp_path), devices=jax.devices()[:1])
        loss = trainer.train_epoch(0)
        trainer.logger.close()
        assert int(trainer.state.step) == 3
        return loss, jax.device_get(
            (trainer.state.dense_params, trainer.state.opt_state,
             trainer.state.tables))

    loss, state = three_steps()
    plain_products(monkeypatch)
    want_loss, want = three_steps()
    assert loss == want_loss
    for (path, a), b in zip(jax.tree.leaves_with_path(state),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=str(path))


def test_the_step_makes_every_large_products_operands_once(tmp_path):
    """The step's barriers, each over ONE array and none over a tree: one a
    site over the cotangent of a product's output (the ``dy`` that ``dx``
    and ``dw`` both read: ``cotangents_once``), and one a site over a left
    operand that is an expression, there twice (the forward pass and the
    rematerialised one, whose array the weight-gradient product reads:
    ``operands_once``).  A later edit cannot put an expression back inside a
    product in silence, nor hold every cotangent alive at once.  PR 33's
    barrier over each leaf's weight gradient is gone: with the operands
    made once the step measured faster with the product and AdamW's sweep
    as one fusion (``models/olmo_hybrid.cotangent_once``)."""
    from tdfo_tpu.train.trainer import Trainer

    trainer = Trainer(trainer_config(tmp_path, lm=LM),
                      devices=jax.devices()[:1])
    batch = {k: jnp.zeros((2, 48), jnp.int32) for k in ("token", "segment")}
    jaxpr = jax.make_jaxpr(trainer.train_step)(
        trainer.state, batch, trainer._dropout_rng)
    trainer.logger.close()
    held = barriers(jaxpr.jaxpr)
    assert all(len(shapes) == 1 for shapes in held), held
    cfg = trainer.model_cfg
    cotangents, operands = cotangents_once(cfg, 2, 48), operands_once(cfg, 2, 48)
    assert len(cotangents) == 1 + 12 + 6 and len(operands) == 4 + 3
    assert sorted(shapes[0] for shapes in held) == sorted(cotangents + 2 * operands)
    # no barrier has a weight's shape: no leaf's gradient is held apart
    leaves = {a.shape for a in jax.tree.leaves(trainer.state.dense_params)}
    assert not leaves & {shapes[0] for shapes in held}


@pytest.mark.parametrize("kind", M.LAYER_KINDS)
def test_a_rematerialised_layer_saves_what_the_plain_products_save(
        kind, monkeypatch, capsys):
    from jax.ad_checkpoint import print_saved_residuals

    cfg = model_cfg(layer_types=(kind,))
    params = M.init_params(jax.random.key(3), cfg)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 40, 32)).astype(np.float32)
    _, segment = packed(rng, 2, 40)

    def saved():
        """``(type, what it is)`` of every residual, less where in the
        source it was made."""
        print_saved_residuals(
            lambda p, x: M.backbone(p, x, segment, cfg).sum(), params, x)
        return sorted(line.split(" from ")[0]
                      for line in capsys.readouterr().out.splitlines())

    got = saved()
    plain_products(monkeypatch)
    assert got == saved()
    # the policy keeps the products' outputs (it marks each with a
    # ``reduce_precision``), the new form among them: nothing runs twice
    # that did not before
    kept = [line for line in got if "reduce_precision" in line]
    assert len(kept) == (6 if kind == "full_attention" else 9), got
