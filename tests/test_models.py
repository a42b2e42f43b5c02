"""Model-substrate behaviour: prefill/decode consistency per family,
supernet branch semantics, RoPE variants, sliding window."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import cnn
from repro.models import transformer as tr
from repro.models.layers import apply_rope, cross_entropy, fused_cross_entropy

RNG = jax.random.PRNGKey(0)


def consistency(arch, steps=12, window=0, atol=5e-4):
    cfg = get_config(arch, smoke=True)
    params = tr.init_params(RNG, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, steps), 0,
                              cfg.vocab_size)
    prefix = None
    enc_out = None
    if cfg.family in ("vlm", "audio"):
        prefix = jnp.ones((2, cfg.num_prefix, cfg.d_model), jnp.float32) * 0.1
    full, _, _ = tr.forward(params, cfg, toks, prefix=prefix, window=window)
    if cfg.family == "audio":
        enc_out = tr.encode(params, cfg, prefix)
        prefix_for_cache = None
    cache = tr.prefill_cache(params, cfg, toks[:, :-1], window=window,
                             cache_len=2 * steps,
                             enc_out=enc_out)
    if cfg.family == "vlm":
        pytest.skip("vlm prefill-cache path needs the prefix replay; "
                    "covered by test_vlm_prefix_shapes")
    dec, _ = tr.decode_step(params, cfg, toks[:, -1:], cache, window=window)
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(dec[:, 0]),
                               rtol=1e-3, atol=atol)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "chatglm3-6b",
                                  "starcoder2-3b", "deepseek-67b"])
def test_dense_prefill_decode_consistency(arch):
    consistency(arch)


def test_ssm_prefill_decode_consistency():
    # chunk boundary: steps must be compatible with decode recurrence
    cfg = get_config("mamba2-780m", smoke=True)
    params = tr.init_params(RNG, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    # decode token-by-token and compare with nothing — finite check +
    # recurrent-vs-chunked equivalence is covered in test_kernels; here we
    # check the stack-level decode runs and evolves state
    cache = tr.init_cache(params, cfg, 2, 16)
    outs = []
    for i in range(4):
        logits, cache = tr.decode_step(params, cfg, toks[:, i:i + 1], cache)
        outs.append(logits)
    assert not bool(jnp.isnan(jnp.stack(outs)).any())
    assert bool(jnp.any(cache["layers"]["ssm"]["state"] != 0)) if "ssm" in \
        cache["layers"] else True


def test_ssm_chunked_equals_stepwise():
    """forward (chunked SSD) last-token logits == recurrent decode replay."""
    from repro.models.ssm import CHUNK
    cfg = get_config("mamba2-780m", smoke=True)
    params = tr.init_params(RNG, cfg)
    steps = 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, steps), 0,
                              cfg.vocab_size)
    full, _, _ = tr.forward(params, cfg, toks)
    cache = tr.init_cache(params, cfg, 1, steps)
    for i in range(steps):
        dec, cache = tr.decode_step(params, cfg, toks[:, i:i + 1], cache)
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(dec[:, 0]),
                               rtol=2e-3, atol=2e-3)


def test_hybrid_decode_matches_forward():
    cfg = get_config("zamba2-2.7b", smoke=True)
    params = tr.init_params(RNG, cfg)
    steps = 8
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, steps), 0,
                              cfg.vocab_size)
    full, _, _ = tr.forward(params, cfg, toks)
    cache = tr.init_cache(params, cfg, 1, 16)
    for i in range(steps):
        dec, cache = tr.decode_step(params, cfg, toks[:, i:i + 1], cache)
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(dec[:, 0]),
                               rtol=2e-3, atol=2e-3)


def test_whisper_decode_matches_forward():
    cfg = get_config("whisper-large-v3", smoke=True)
    params = tr.init_params(RNG, cfg)
    steps = 8
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, steps), 0,
                              cfg.vocab_size)
    frames = jax.random.normal(jax.random.PRNGKey(5),
                               (2, cfg.num_prefix, cfg.d_model)) * 0.1
    full, _, _ = tr.forward(params, cfg, toks, prefix=frames)
    enc_out = tr.encode(params, cfg, frames)
    cache = tr.prefill_cache(params, cfg, toks[:, :-1], cache_len=16,
                             enc_out=enc_out)
    dec, _ = tr.decode_step(params, cfg, toks[:, -1:], cache)
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(dec[:, 0]),
                               rtol=1e-3, atol=5e-4)


def test_vlm_prefix_shapes():
    cfg = get_config("internvl2-1b", smoke=True)
    params = tr.init_params(RNG, cfg)
    toks = jnp.zeros((2, 16), jnp.int32)
    patches = jnp.ones((2, cfg.num_prefix, cfg.d_model), jnp.float32)
    logits, _, _ = tr.forward(params, cfg, toks, prefix=patches)
    # logits are over token positions only (prefix stripped)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_sliding_window_restricts_context():
    cfg = get_config("qwen1.5-0.5b", smoke=True).replace(num_layers=1)
    params = tr.init_params(RNG, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 32), 0,
                              cfg.vocab_size)
    full, _, _ = tr.forward(params, cfg, toks, window=0)
    win, _, _ = tr.forward(params, cfg, toks, window=8)
    # early positions (inside window) agree, late positions differ
    np.testing.assert_allclose(np.asarray(full[:, :8]), np.asarray(win[:, :8]),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(full[:, -1] - win[:, -1]).max()) > 1e-4


def test_supernet_branches_differ_and_identity_skips():
    cfg = get_config("qwen1.5-0.5b", smoke=True).replace(supernet=True)
    params = tr.init_params(RNG, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0,
                              cfg.vocab_size)
    outs = {}
    for b in range(4):
        key = jnp.full((cfg.num_layers,), b, jnp.int32)
        outs[b], _, _ = tr.forward(params, cfg, toks, choice_key=key)
    # all four branches give distinct outputs
    for i in range(4):
        for j in range(i + 1, 4):
            assert float(jnp.abs(outs[i] - outs[j]).max()) > 1e-5, (i, j)
    # all-identity == embedding -> final norm -> unembed (no layer effect):
    # compare against a 0-layer model with the same embedding
    cfg0 = cfg.replace(num_layers=0, supernet=False)
    p0 = {"embed": params["embed"], "final_ln": params["final_ln"],
          "layers": jax.tree.map(lambda x: x[:0],
                                 jax.tree.map(lambda x: x[:, 0], params["layers"]))}
    out0, _, _ = tr.forward(p0, cfg0, toks)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(out0),
                               rtol=1e-4, atol=1e-5)


def test_rope_2d_rotates_half():
    x = jax.random.normal(RNG, (1, 8, 2, 16))
    pos = jnp.arange(8)[None, :]
    full = apply_rope(x, pos, style="1d")
    half = apply_rope(x, pos, style="2d")
    # 2d: second half of head dim is pass-through
    np.testing.assert_allclose(np.asarray(half[..., 8:]),
                               np.asarray(x[..., 8:]))
    assert float(jnp.abs(full[..., 8:] - x[..., 8:]).max()) > 1e-4
    # position 0 unrotated everywhere
    np.testing.assert_allclose(np.asarray(full[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-5, atol=1e-6)


def test_chunked_attention_backend_matches_xla():
    cfg = get_config("chatglm3-6b", smoke=True)   # GQA kv=2 + 2d rope
    params = tr.init_params(RNG, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(11), (2, 100), 0,
                              cfg.vocab_size)
    lx, _, _ = tr.forward(params, cfg, toks, backend="xla")
    lc, _, _ = tr.forward(params, cfg, toks, backend="chunked")
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lc), rtol=1e-5,
                               atol=1e-5)
    lxw, _, _ = tr.forward(params, cfg, toks, backend="xla", window=16)
    lcw, _, _ = tr.forward(params, cfg, toks, backend="chunked", window=16)
    np.testing.assert_allclose(np.asarray(lxw), np.asarray(lcw), rtol=1e-5,
                               atol=1e-5)


def test_fused_ce_matches_naive():
    rng = jax.random.PRNGKey(8)
    h = jax.random.normal(rng, (2, 32, 64))
    table = jax.random.normal(jax.random.PRNGKey(9), (100, 64)) * 0.1
    labels = jax.random.randint(jax.random.PRNGKey(10), (2, 32), 0, 100)
    naive = cross_entropy(jnp.einsum("bsd,vd->bsv", h, table), labels)
    fused = fused_cross_entropy(h, table, labels, chunk=16)
    np.testing.assert_allclose(float(naive), float(fused), rtol=1e-5)
    # gradients agree too
    g1 = jax.grad(lambda t: cross_entropy(
        jnp.einsum("bsd,vd->bsv", h, t), labels))(table)
    g2 = jax.grad(lambda t: fused_cross_entropy(h, t, labels, chunk=16))(table)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-6)


def _nine_tap(x, w, stride):
    """Depthwise 3x3 by definition: pad one pixel on each side, then
    output pixel i sums inputs stride*i - 1 .. stride*i + 1."""
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    ho = (x.shape[1] - 1) // stride + 1
    wo = (x.shape[2] - 1) // stride + 1
    out = 0.0
    for a in range(3):
        for b in range(3):
            tap = xp[:, a: a + stride * (ho - 1) + 1: stride,
                     b: b + stride * (wo - 1) + 1: stride, :]
            out = out + tap * w[a, b, 0]
    return out


@pytest.mark.parametrize("form", ["grouped", "taps", "platform"])
@pytest.mark.parametrize("channels", [8, 64])
@pytest.mark.parametrize("stride", [1, 2])
def test_depthwise_matches_nine_tap_definition(stride, channels, form):
    """Each formulation of ``cnn._depthwise`` (and the one this platform
    compiles) against the definition: values, both gradients, and the
    stride-2 window pinned by a one-hot input."""
    depthwise = {"grouped": cnn._depthwise_grouped,
                 "taps": cnn._depthwise_taps,
                 "platform": cnn._depthwise}[form]
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(stride * channels), 3)
    x = jax.random.normal(kx, (3, 8, 8, channels), jnp.float32)
    w = jax.random.uniform(kw, (3, 3, 1, channels), jnp.float32, -1.0, 1.0)
    got = depthwise(x, w, stride)
    want = _nine_tap(x, w, stride)
    assert got.shape == want.shape == (3, 8 // stride, 8 // stride, channels)

    def close(a, b):
        # float32 sums: entries that cancel near zero are held to the
        # array's own scale
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5,
                                   atol=1e-6 * np.abs(b).max())

    close(got, want)
    g = jax.random.normal(kg, got.shape, jnp.float32)
    grads = [jax.grad(lambda x, w: jnp.sum(f(x, w, stride) * g),
                      argnums=(0, 1))(x, w)
             for f in (depthwise, _nine_tap)]
    for a, b in zip(*grads):
        close(a, b)
    # a one-hot input pins the window: input pixel (3, 3) reaches output
    # pixels 1 and 2 on each axis (windows 2i-1 .. 2i+1 at stride 2,
    # i-1 .. i+1 at stride 1), through the mirrored taps
    hot = jnp.zeros((1, 8, 8, channels)).at[0, 3, 3, :].set(1.0)
    out = np.asarray(depthwise(hot, w, stride))[0]
    seen = [i for i in range(out.shape[0]) if 3 - stride * i + 1 in (0, 1, 2)]
    assert seen == ([1, 2] if stride == 2 else [2, 3, 4])
    nz = np.argwhere(np.abs(out).sum(-1) > 0)
    assert sorted(map(tuple, nz)) == [(i, j) for i in seen for j in seen]
    for i in seen:
        for j in seen:
            a, b = 3 - stride * i + 1, 3 - stride * j + 1
            np.testing.assert_array_equal(out[i, j], np.asarray(w[a, b, 0]))
