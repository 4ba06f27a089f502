"""Model-level Pallas kernels (WKV recurrence, flash attention) vs their
pure-jnp or dense oracles, swept over shapes."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.wkv import wkv_pallas
from repro.models.rwkv import wkv_chunked

# interpret-mode Pallas / full-model tests: minutes of wall clock on CPU
pytestmark = pytest.mark.slow



def _tr(x):
    return x.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("B,H,S,hd,chunk", [
    (2, 3, 128, 64, 64), (1, 1, 64, 64, 64), (2, 2, 256, 32, 32),
    (1, 4, 192, 64, 64),
])
def test_wkv_pallas_matches_chunked_ref(B, H, S, hd, chunk):
    ks = jax.random.split(jax.random.key(B * 1000 + S), 6)
    r = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, hd), jnp.float32) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, hd, hd), jnp.float32) * 0.1
    y_ref, s_ref = wkv_chunked(r, k, v, logw, u, s0, chunk)
    y_p, s_p = wkv_pallas(_tr(r), _tr(k), _tr(v), _tr(logw), u, s0,
                          chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(_tr(y_ref)), np.asarray(y_p),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s_p),
                               rtol=1e-4, atol=1e-4)


def test_wkv_pallas_state_chain():
    """Splitting a sequence into two pallas calls (carrying the state)
    equals one call over the concatenation."""
    B, H, S, hd = 1, 2, 128, 64
    ks = jax.random.split(jax.random.key(7), 5)
    mk = lambda i, scale=0.5: jax.random.normal(ks[i], (B, H, S, hd)) * scale
    r, k, v = mk(0), mk(1), mk(2)
    logw = -jnp.exp(mk(3) * 0.3 - 2.0)
    u = jnp.zeros((H, hd))
    s0 = jnp.zeros((B, H, hd, hd))
    y_full, s_full = wkv_pallas(r, k, v, logw, u, s0, chunk=64,
                                interpret=True)
    half = S // 2
    y1, s1 = wkv_pallas(r[:, :, :half], k[:, :, :half], v[:, :, :half],
                        logw[:, :, :half], u, s0, chunk=64, interpret=True)
    y2, s2 = wkv_pallas(r[:, :, half:], k[:, :, half:], v[:, :, half:],
                        logw[:, :, half:], u, s1, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y_full[:, :, half:]),
                               np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,S,di,n,chunk,di_block", [
    (2, 256, 256, 16, 64, 128), (1, 128, 128, 8, 128, 128),
    (2, 192, 512, 16, 64, 256),
])
def test_ssm_scan_pallas_matches_ref(B, S, di, n, chunk, di_block):
    from repro.kernels.ssm_scan import ssm_scan_pallas
    from repro.models.mamba import _ssm_scan_chunked
    ks = jax.random.split(jax.random.key(S + di), 4)
    decay = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, di, n)))
    bx = jax.random.normal(ks[1], (B, S, di, n)) * 0.3
    c_t = jax.random.normal(ks[2], (B, S, n)) * 0.5
    h0 = jax.random.normal(ks[3], (B, di, n)) * 0.1
    states, h_ref = _ssm_scan_chunked(decay, bx, h0, chunk)
    y_ref = jnp.einsum("bsdn,bsn->bsd", states, c_t)
    tr = lambda x: x.transpose(0, 1, 3, 2)
    y_p, h_p = ssm_scan_pallas(tr(decay), tr(bx), c_t,
                               h0.transpose(0, 2, 1), chunk=chunk,
                               di_block=di_block, interpret=True)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_p.transpose(0, 2, 1)),
                               np.asarray(h_ref), rtol=1e-4, atol=1e-4)


def test_pallas_dispatch_in_model():
    """cfg.use_pallas='always' routes gqa_forward through the Pallas kernel
    (kernel forward, kernel backward) with matching grads."""
    from repro.configs import get_config
    from repro.models import attention as a
    cfg = get_config("stablelm-3b").smoke().replace(attn_chunk=128,
                                                    head_dim=32)
    cfg_p = cfg.replace(use_pallas="always")
    p = a.init_gqa(jax.random.key(0), cfg, 0)
    x = jax.random.normal(jax.random.key(1), (2, 128, cfg.d_model)) * 0.3
    jaxpr = jax.make_jaxpr(lambda xx: a.gqa_forward(p, xx, cfg_p)[0])(x)
    assert "pallas_call" in str(jaxpr)
    out_ref, _ = a.gqa_forward(p, x, cfg)
    out_pal, _ = a.gqa_forward(p, x, cfg_p)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_pal),
                               rtol=2e-4, atol=2e-4)
    g_ref = jax.grad(lambda xx: a.gqa_forward(p, xx, cfg)[0].sum())(x)
    g_pal = jax.grad(lambda xx: a.gqa_forward(p, xx, cfg_p)[0].sum())(x)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_pal),
                               rtol=2e-4, atol=2e-4)


def _dense_attention(q, k, v, causal):
    """f32 softmax over the whole (Sq, Skv) score matrix; GQA by repeat."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / math.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,d,causal,dtype,tol", [
    (2, 2, 2, 200, 300, 64, False, jnp.float32, 1e-4),   # cross-attention shape
    (1, 2, 2, 300, 300, 64, False, jnp.float32, 1e-4),
    (1, 2, 2, 200, 200, 64, True, jnp.float32, 1e-4),
    (1, 2, 2, 256, 256, 64, True, jnp.float32, 1e-4),    # aligned: no padding
    (1, 4, 2, 200, 200, 64, True, jnp.float32, 1e-4),    # GQA
    (1, 1, 1, 100, 600, 64, False, jnp.float32, 1e-4),   # one kv block, 5 tiles
    (1, 1, 1, 100, 2200, 64, False, jnp.float32, 1e-4),  # 9 kv blocks of 256
    (1, 1, 1, 200, 300, 64, False, jnp.bfloat16, 3e-2),
    (1, 1, 1, 3072, 3072, 80, True, jnp.float32, 1e-4),  # 3 q, kv blocks of 1024
], ids=["cross", "self", "causal", "aligned", "gqa", "kv-tiles", "kv-blocks",
        "bf16", "causal-long-hd80"])
def test_flash_kernel_padded_matches_softmax(B, Hq, Hkv, Sq, Skv, d, causal,
                                             dtype, tol):
    """The padded kernel path's output and dq, dk, dv against a dense f32
    softmax (tolerances relative to each array's largest entry)."""
    from repro.models.attention import _flash_pallas
    ks = jax.random.split(jax.random.key(Sq * 7 + Skv + Hq), 4)
    q = jax.random.normal(ks[0], (B, Hq, Sq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, d)).astype(dtype)
    do = jax.random.normal(ks[3], (B, Hq, Sq, d))
    f32 = lambda x: x.astype(jnp.float32)
    out, vjp = jax.vjp(lambda *a: _flash_pallas(*a, causal), q, k, v)
    ref, vjp_ref = jax.vjp(lambda *a: _dense_attention(*a, causal),
                           f32(q), f32(k), f32(v))
    grads = vjp(do.astype(dtype))
    grads_ref = vjp_ref(do)
    for got, want in zip((out, *grads), (ref, *grads_ref)):
        assert got.shape == want.shape and got.dtype == dtype
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(want),
                                   rtol=0, atol=tol * scale)


def test_pallas_dispatch_in_whisper():
    """use_pallas='always' puts the kernel in whisper's encoder
    self-attention, cross-attention and decoder self-attention, and the
    loss and parameter gradients (remat on) match use_pallas='never'."""
    from repro.configs import get_config
    from repro.models import attention as a
    from repro.models import whisper as w
    cfg = get_config("whisper-base").smoke().replace(remat=True,
                                                     use_pallas="never")
    cfg_p = cfg.replace(use_pallas="always")
    params = w.init_model(jax.random.key(0), cfg)
    B, S = 2, 24
    ks = jax.random.split(jax.random.key(1), 3)
    batch = {"frames": jax.random.normal(ks[0], (B, cfg.encoder_seq,
                                                 cfg.d_model)) * 0.3,
             "tokens": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(ks[2], (B, S), 0, cfg.vocab_size)}
    layer0 = jax.tree_util.tree_map(lambda x: x[0], params["dec_blocks"])
    enc = w.encode(params, batch["frames"], cfg)
    h = jax.random.normal(ks[0], (B, S, cfg.d_model)) * 0.3
    xk, xv = w._cross_kv(layer0["xattn"], enc, cfg)
    jaxprs = {
        "encode": jax.make_jaxpr(
            lambda f: w.encode(params, f, cfg_p))(batch["frames"]),
        "cross": jax.make_jaxpr(
            lambda x: w._cross_attend(layer0["xattn"], x, xk, xv, cfg_p))(h),
        "decoder": jax.make_jaxpr(
            lambda x: a.gqa_forward(layer0["attn"], x, cfg_p)[0])(h),
    }
    for name, jaxpr in jaxprs.items():
        assert "pallas_call" in str(jaxpr), name
    loss = jax.value_and_grad(lambda p, c: w.loss_fn(p, batch, c)[0])
    l_ref, g_ref = loss(params, cfg)
    l_pal, g_pal = loss(params, cfg_p)
    np.testing.assert_allclose(float(l_pal), float(l_ref), rtol=2e-4)
    for x, y in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_pal)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-4)


def _weight_dots(jaxpr, weights):
    """Yield every dot_general in ``jaxpr`` (sub-jaxprs included) with an
    operand derived from a var in ``weights``, by casts, reshapes, slices
    and transposes, through scan and remat bodies."""
    from jax.extend.core import ClosedJaxpr, Jaxpr, Var
    follow = {"convert_element_type", "reshape", "transpose", "squeeze",
              "broadcast_in_dim", "slice", "dynamic_slice", "copy"}
    for eqn in jaxpr.eqns:
        hit = [isinstance(v, Var) and v in weights for v in eqn.invars]
        if eqn.primitive.name == "dot_general" and any(hit):
            yield eqn
        if eqn.primitive.name in follow and any(hit):
            weights.update(eqn.outvars)
        for sub in eqn.params.values():
            sub = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
            if isinstance(sub, Jaxpr) and len(sub.invars) == len(eqn.invars):
                yield from _weight_dots(
                    sub, {v for v, h in zip(sub.invars, hit) if h})


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_whisper_encoder_runs_in_config_dtype(dtype, monkeypatch):
    """f32 frames from the stub frontend enter whisper's encoder in
    cfg.dtype: its output, the q/k/v reaching attention and every matmul
    against a weight are in that dtype, so a bfloat16 model's encoder does
    not promote to float32."""
    from repro.configs import get_config
    from repro.models import whisper as w
    cfg = get_config("whisper-base").smoke().replace(dtype=dtype)
    want = jnp.dtype(dtype)
    params = w.init_model(jax.random.key(0), cfg)
    frames = jax.random.normal(jax.random.key(1),
                               (2, cfg.encoder_seq, cfg.d_model), jnp.float32)
    seen, real = [], w.flash_attention

    def recording(q, k, v, **kw):
        seen.append((q.dtype, k.dtype, v.dtype))
        return real(q, k, v, **kw)

    monkeypatch.setattr(w, "flash_attention", recording)
    out = w.encode(params, frames, cfg)
    assert out.dtype == want
    assert seen and all(d == (want,) * 3 for d in seen), seen

    closed = jax.make_jaxpr(lambda p, f: w.encode(p, f, cfg))(params, frames)
    n_weights = len(jax.tree_util.tree_leaves(params))
    dots = list(_weight_dots(closed.jaxpr,
                             set(closed.jaxpr.invars[:n_weights])))
    assert len(dots) == 7                               # q, k, v, o, 3 mlp
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [want, want], eqn


def test_whisper_prefill_cross_cache_matches_cache_spec():
    """prefill's cross-attention cache (xk, xv), built from f32 frames, has
    the dtype and shape cache_spec declares."""
    from repro.configs import get_config
    from repro.models import whisper as w
    cfg = get_config("whisper-base").smoke().replace(dtype="bfloat16")
    params = w.init_model(jax.random.key(0), cfg)
    B, S = 2, 8
    frames = jax.random.normal(jax.random.key(1),
                               (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    tokens = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    _, caches = jax.eval_shape(lambda p, t, f: w.prefill(p, t, f, cfg),
                               params, tokens, frames)
    spec = w.cache_spec(cfg, B, S)
    for name in ("xk", "xv"):
        shape, dtype = spec[name]
        assert (caches[name].shape, caches[name].dtype) == (shape, dtype), name


def test_pallas_dispatch_mamba():
    """use_pallas routes the mamba scan through kernels/ssm_scan with
    matching forward and (reference-backward) gradients."""
    from repro.configs import get_config
    from repro.models import mamba as m
    cfg = get_config("jamba-v0.1-52b").smoke().replace(mamba_fused_y=True)
    cfg_p = cfg.replace(use_pallas="always")
    p = m.init_mamba(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model)) * 0.4
    y0, _ = m.mamba_mixer(p, x, cfg)
    y1, _ = m.mamba_mixer(p, x, cfg_p)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-4, atol=1e-5)
    g0 = jax.grad(lambda xx: m.mamba_mixer(p, xx, cfg)[0].sum())(x)
    g1 = jax.grad(lambda xx: m.mamba_mixer(p, xx, cfg_p)[0].sum())(x)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=1e-4, atol=1e-5)
    jaxpr = jax.make_jaxpr(lambda xx: m.mamba_mixer(p, xx, cfg_p)[0])(x)
    assert "pallas_call" in str(jaxpr)


def test_pallas_dispatch_rwkv():
    """use_pallas routes WKV through kernels/wkv end-to-end (loss parity;
    grads within fp32 reordering noise)."""
    from repro.configs import get_config
    from repro.models.registry import get_model
    cfg = get_config("rwkv6-1.6b").smoke()
    api = get_model(cfg)
    api_p = get_model(cfg.replace(use_pallas="always"))
    params = api.init(jax.random.key(0))
    batch = {"tokens": jnp.ones((2, 64), jnp.int32),
             "labels": jnp.ones((2, 64), jnp.int32)}
    l0, _ = api.loss_fn(params, batch)
    l1, _ = api_p.loss_fn(params, batch)
    assert abs(float(l0) - float(l1)) < 1e-5
    g0 = jax.grad(lambda p: api.loss_fn(p, batch)[0])(params)
    g1 = jax.grad(lambda p: api_p.loss_fn(p, batch)[0])(params)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-3)


def _smoke_loss(model, use_pallas, remat_policy):
    """(loss(params), params) of a two-layer smoke model with remat on: the
    transformer is StableLM's block (one causal attention a layer), whisper
    has three (encoder, decoder and cross-attention)."""
    from repro.configs import get_config
    from repro.models import transformer as t
    from repro.models import whisper as w
    B, S = 2, 128
    ks = jax.random.split(jax.random.key(1), 3)
    tokens = {"tokens": jax.random.randint(ks[1], (B, S), 0, 512),
              "labels": jax.random.randint(ks[2], (B, S), 0, 512)}
    if model == "whisper":
        cfg = get_config("whisper-base").smoke()
        lib, init = w, w.init_model
        batch = {"frames": jax.random.normal(
            ks[0], (B, cfg.encoder_seq, cfg.d_model)) * 0.3, **tokens}
    else:
        cfg = get_config("stablelm-3b").smoke()
        lib, init, batch = t, t.init_decoder, tokens
    cfg = cfg.replace(remat=True, remat_policy=remat_policy,
                      use_pallas=use_pallas)
    params = init(jax.random.key(0), cfg)
    return (lambda p: lib.loss_fn(p, batch, cfg)[0]), params


def _plain_checkpoint(body, cfg):
    """The reference for ``attention.remat``: a checkpoint that keeps no
    named value (under ``dots``, matmul outputs only)."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and its sub-jaxprs, in order."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            for s in sub if isinstance(sub, (tuple, list)) else (sub,):
                s = s.jaxpr if isinstance(s, ClosedJaxpr) else s
                if isinstance(s, Jaxpr):
                    yield from _eqns(s)


def _splash_forwards(closed):
    return sum(eqn.primitive.name == "pallas_call"
               and "splash_mha_fwd" in eqn.params["name"]
               for eqn in _eqns(closed.jaxpr))


def _grad_and_jaxpr(model, use_pallas, remat_policy, monkeypatch, plain):
    from repro.models import attention as a
    loss, params = _smoke_loss(model, use_pallas, remat_policy)
    with monkeypatch.context() as m:
        if plain:
            m.setattr(a, "remat", _plain_checkpoint)
        return (jax.jit(jax.grad(loss))(params),
                jax.make_jaxpr(jax.grad(loss))(params))


@pytest.mark.parametrize("remat_policy", ["full", "dots"])
@pytest.mark.parametrize("model,sites", [("whisper", 3), ("transformer", 1)])
def test_remat_keeps_splash_residuals(model, sites, remat_policy, monkeypatch):
    """Under remat the backward reads the splash forward's saved out and
    logsumexp: one forward kernel per attention site in the gradient's
    jaxpr, against two under a plain checkpoint, with the same gradients."""
    g, jaxpr = _grad_and_jaxpr(model, "always", remat_policy, monkeypatch,
                               plain=False)
    g_plain, jaxpr_plain = _grad_and_jaxpr(model, "always", remat_policy,
                                           monkeypatch, plain=True)
    assert _splash_forwards(jaxpr) == sites
    assert _splash_forwards(jaxpr_plain) == 2 * sites
    for x, y in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_plain)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("remat_policy", ["full", "dots"])
@pytest.mark.parametrize("model", ["whisper", "transformer"])
def test_remat_without_kernel_is_plain_checkpoint(model, remat_policy,
                                                  monkeypatch):
    """On the jnp attention path nothing carries the residuals' name, so the
    gradient's jaxpr under ``attention.remat`` holds the same equations as
    under a plain checkpoint, and the gradients are the same."""
    g, jaxpr = _grad_and_jaxpr(model, "never", remat_policy, monkeypatch,
                               plain=False)
    g_plain, jaxpr_plain = _grad_and_jaxpr(model, "never", remat_policy,
                                           monkeypatch, plain=True)
    sig = lambda j: [(e.primitive.name, [str(v.aval) for v in e.outvars])
                     for e in _eqns(j.jaxpr)]
    assert sig(jaxpr) == sig(jaxpr_plain)
    assert _splash_forwards(jaxpr) == 0
    for x, y in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_plain)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
