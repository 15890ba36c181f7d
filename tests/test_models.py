"""Per-architecture smoke tests (reduced configs, CPU) + decode consistency.

Each assigned arch instantiates a REDUCED config of the same family and runs
one forward/train step asserting output shapes and finiteness; decode paths
must agree with the full forward (exact for deterministic families,
tolerance for capacity-dropping MoE).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS, applicable_shapes
from repro.data import DataConfig, synthetic_batch
from repro.models import decode_step, forward, init_cache, init_params, prefill
from repro.train import adamw, init_train_state, make_train_step

ARCHS = sorted(CONFIGS)


def _batch_for(cfg, B, S, seed=0):
    key = jax.random.PRNGKey(seed)
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    if cfg.frontend == "vision_stub":
        batch["prefix_embed"] = (
            jax.random.normal(key, (B, cfg.num_prefix_tokens, cfg.d_model)) * 0.1
        )
    if cfg.family == "encdec":
        batch["audio_frames"] = (
            jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model)) * 0.1
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = CONFIGS[arch].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 24
    batch = _batch_for(cfg, B, S)
    logits, aux, _ = forward(params, cfg, batch)
    S_total = S + (cfg.num_prefix_tokens if cfg.frontend == "vision_stub" else 0)
    assert logits.shape == (B, S_total, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = CONFIGS[arch].reduced()
    opt = adamw(1e-3)
    state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = make_train_step(cfg, opt)
    dcfg = DataConfig(seq_len=16, global_batch=2, vocab_size=cfg.vocab_size)
    batch = synthetic_batch(dcfg, 0, cfg)
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    before = jax.tree_util.tree_leaves(state.params)[0]
    after = jax.tree_util.tree_leaves(new_state.params)[0]
    assert not np.allclose(before, after)


def _prefill_rows(params, cfg, batch_full, plens, max_len):
    """Prefill each row alone at its own prompt length and stack the rows'
    caches along each leaf's batch axis (found by tracing prefill at batch
    sizes 1 and 2): the rows then stand at different positions, as slots
    of a continuous batch do.  Returns (cache, (B,) cache_len)."""

    def row_batch(b, n):
        batch = {k: v[b : b + 1] for k, v in batch_full.items()}
        batch["tokens"] = batch["tokens"][:, :n]
        return batch

    def cache_shape(nb):
        batch = {k: jnp.concatenate([v] * nb) for k, v in row_batch(0, plens[0]).items()}
        cache = init_cache(cfg, nb, max_len=max_len, cache_dtype=jnp.float32)
        return jax.eval_shape(lambda: prefill(params, cfg, batch, cache)[1])

    axes = jax.tree_util.tree_map(
        lambda a, b: next(i for i, (m, n) in enumerate(zip(a.shape, b.shape)) if m != n),
        cache_shape(1), cache_shape(2),
    )
    caches, lens = [], []
    for b, n in enumerate(plens):
        cache = init_cache(cfg, 1, max_len=max_len, cache_dtype=jnp.float32)
        _, cache, clen = prefill(params, cfg, row_batch(b, n), cache)
        caches.append(cache)
        lens.append(int(clen))
    cache = jax.tree_util.tree_map(lambda ax, *xs: jnp.concatenate(xs, ax), axes, *caches)
    return cache, jnp.asarray(lens, jnp.int32)


def _decode_against_forward(arch, per_row, n_dec=3):
    """Prefill, then decode `n_dec` tokens teacher-forced from one sequence
    per row; returns [(decode logits (B, V), full-forward logits (B, V))]
    for the prefill's last token and each decoded one.

    `per_row` False: both rows prefill 12 tokens together and decode with a
    scalar cache_len.  True: rows prefill 5 and 12 tokens alone and decode
    with a (B,) cache_len; the longer row's last write lands at max_len - 2,
    the last position a continuous-batching slot writes."""
    cfg = CONFIGS[arch].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    prefix = cfg.num_prefix_tokens if cfg.frontend == "vision_stub" else 0
    plens = (5, 12) if per_row else (12, 12)
    batch_full = _batch_for(cfg, len(plens), max(plens) + n_dec, seed=1)
    logits_full, _, _ = forward(params, cfg, batch_full)
    rows = np.arange(len(plens))

    def full_at(t):  # forward logits at each row's t-th token past its prompt
        return logits_full[rows, prefix + np.asarray(plens) + t]

    if per_row:
        max_len = prefix + max(plens) + n_dec + 1
        cache, clen = _prefill_rows(params, cfg, batch_full, plens, max_len)
        lg = None
    else:
        cache = init_cache(cfg, 2, max_len=prefix + 12 + n_dec + 4, cache_dtype=jnp.float32)
        batch_pre = dict(batch_full)
        batch_pre["tokens"] = batch_full["tokens"][:, :12]
        lg, cache, clen = prefill(params, cfg, batch_pre, cache)
        lg = lg[:, -1]
    pairs = [] if lg is None else [(lg, full_at(-1))]
    for t in range(n_dec):
        tok = batch_full["tokens"][rows, np.asarray(plens) + t][:, None]
        lg, cache = decode_step(params, cfg, tok, cache, clen)
        clen = clen + 1
        pairs.append((lg[:, 0], full_at(t)))
    if per_row:
        assert int(clen.max()) - 1 == max_len - 2
    return pairs


CACHE_LEN_FORMS = pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])


@CACHE_LEN_FORMS
@pytest.mark.parametrize(
    "arch",
    [a for a in ARCHS if CONFIGS[a].moe is None],  # MoE: capacity drops differ
)
def test_decode_matches_forward_exactly(arch, per_row):
    for got, want in _decode_against_forward(arch, per_row):
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


@CACHE_LEN_FORMS
@pytest.mark.parametrize("arch", [a for a in ARCHS if CONFIGS[a].moe is not None])
def test_decode_close_for_moe(arch, per_row):
    """Capacity-based MoE may drop different tokens at different batch
    compositions (known train/serve property); require closeness only."""
    for got, want in _decode_against_forward(arch, per_row):
        # rank correlation of top prediction rather than exact equality
        top_full = np.asarray(jnp.argmax(want, -1))
        top_dec = np.asarray(jnp.argmax(got, -1))
        assert (top_full == top_dec).mean() >= 0.5
        assert float(jnp.max(jnp.abs(got - want))) < 0.2


def test_param_counts_match_published_sizes():
    expect = {
        "llama3-405b": 405e9,
        "llama3-8b": 8.0e9,
        "gemma2-27b": 27.2e9,
        "qwen3-32b": 32.8e9,
        "deepseek-v3-671b": 671e9,
        "olmoe-1b-7b": 6.9e9,
        "zamba2-1.2b": 1.2e9,
    }
    for arch, target in expect.items():
        total, _ = CONFIGS[arch].param_count()
        assert abs(total - target) / target < 0.06, (arch, total)


def test_moe_active_params():
    total, active = CONFIGS["deepseek-v3-671b"].param_count()
    assert active < total * 0.08  # ~37B of 671B
    total, active = CONFIGS["olmoe-1b-7b"].param_count()
    assert active < total * 0.25


def test_shape_applicability():
    for arch, cfg in CONFIGS.items():
        names = {s.name for s in applicable_shapes(cfg)}
        if cfg.family in ("hybrid", "ssm"):
            assert "long_500k" in names, arch
        else:
            assert "long_500k" not in names, arch
        assert {"train_4k", "prefill_32k", "decode_32k"} <= names


def test_gemma2_local_global_pattern():
    cfg = CONFIGS["gemma2-27b"]
    kinds = cfg.layer_kinds()
    assert kinds[0] == "attn_local" and kinds[1] == "attn_global"
    assert len(kinds) == 46


def test_vlm_prefix_changes_text_logits():
    cfg = CONFIGS["internvl2-1b"].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg, 1, 8, seed=2)
    l1, _, _ = forward(params, cfg, batch)
    batch2 = dict(batch)
    batch2["prefix_embed"] = batch["prefix_embed"] + 1.0
    l2, _, _ = forward(params, cfg, batch2)
    # causal: prefix influences text positions
    assert float(jnp.max(jnp.abs(l1[:, -1] - l2[:, -1]))) > 1e-4


def test_whisper_encoder_affects_decoder():
    cfg = CONFIGS["whisper-large-v3"].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg, 1, 8, seed=3)
    l1, _, _ = forward(params, cfg, batch)
    batch2 = dict(batch)
    batch2["audio_frames"] = batch["audio_frames"] * -1.0
    l2, _, _ = forward(params, cfg, batch2)
    assert float(jnp.max(jnp.abs(l1 - l2))) > 1e-4
