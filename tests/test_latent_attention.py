"""Latent attention (`layers/latent_attention.py`) against the plain
reference (`tests/reference_glm_moe_lite.py`): values and every gradient on
seeded weights over packed documents; the rotary key that all heads share
against explicit copies of it, one a head; the published heads of 256 (192 +
64 for ``q k^T``, 256 for ``P v``) through both attention paths, the kernel in
Pallas's interpreter, and its lowering for the TPU at 20 such heads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_glm_moe_lite as ref
from distributed_embeddings_tpu.layers import latent_attention as la
from distributed_embeddings_tpu.layers.attention import (
    Causal,
    attention_splash,
    attention_xla,
)

TOY = la.LatentShapes(heads=4, q_rank=12, kv_rank=8, nope=6, rope=4, v=10,
                      eps=1e-5, theta=1e4)
HIDDEN = 32


def _rcfg(shapes):
  return dict(num_attention_heads=shapes.heads, q_lora_rank=shapes.q_rank,
              kv_lora_rank=shapes.kv_rank, qk_nope_head_dim=shapes.nope,
              qk_rope_head_dim=shapes.rope, v_head_dim=shapes.v,
              rms_norm_eps=shapes.eps, rope_theta=shapes.theta,
              mean_document_length=1)


def _case(shapes, hidden, length, batch=2, starts_at=(5, 17), seed=0,
          scale=0.3):
  rng = np.random.default_rng(seed)
  p = {n: jnp.asarray(rng.uniform(0.8, 1.2, shape) if kind == "gain"
                      else rng.uniform(-scale, scale, shape), jnp.float32)
       for n, (shape, kind) in shapes.leaves(hidden).items()}
  h = jnp.asarray(rng.normal(size=(batch, length, hidden)), jnp.float32)
  starts = np.zeros((batch, length), bool)
  starts[:, 0] = True
  starts[0, list(starts_at)] = True
  return p, h, jnp.asarray(starts)


def _ours(shapes, p, h, starts, attend=attention_xla):
  seg = jnp.cumsum(starts, axis=1).astype(jnp.int32) - 1
  return la.latent_attention(shapes, p, h, jnp.arange(h.shape[1]), seg,
                             attend)


def test_the_leaves_are_the_published_shapes():
  full = la.LatentShapes(20, 768, 512, 192, 64, 256, 1e-5, 1e6)
  shapes = {n: s for n, (s, _) in full.leaves(2048).items()}
  assert shapes == {"w_dq": (2048, 768), "q_a_norm": (768,),
                    "w_uq": (768, 5120), "w_dkv": (2048, 576),
                    "kv_a_norm": (512,), "w_ukv": (512, 8960),
                    "w_o": (5120, 2048)}
  assert sum(int(np.prod(s)) for s in shapes.values()) == 21759232


@pytest.mark.parametrize("length,starts_at", [(24, (5, 17)), (21, ()),
                                              (33, (1, 2, 30))])
def test_the_layer_is_the_plain_reference(length, starts_at):
  p, h, starts = _case(TOY, HIDDEN, length, starts_at=starts_at)
  rcfg = _rcfg(TOY)
  f = lambda fn: jax.jit(jax.value_and_grad(
      lambda p, h: jnp.sum(jnp.sin(fn(p, h))), argnums=(0, 1)))
  with jax.default_matmul_precision("highest"):
    got = _ours(TOY, p, h, starts)
    want = ref.attention(rcfg, p, h, starts)
    (_, (gp, gh)) = f(lambda p, h: _ours(TOY, p, h, starts))(p, h)
    (_, (wp, wh)) = f(lambda p, h: ref.attention(rcfg, p, h, starts))(p, h)
  assert got.shape == h.shape
  np.testing.assert_allclose(got, want,
                             atol=2e-5 * float(jnp.max(jnp.abs(want))))
  assert set(gp) == set(wp)
  for name, w in {**wp, "h": wh}.items():
    g = gh if name == "h" else gp[name]
    scale = float(jnp.max(jnp.abs(w)))
    assert scale > 0, name
    np.testing.assert_allclose(g, w, atol=2e-5 * scale, err_msg=name)


def test_the_shared_rotary_key_is_twenty_explicit_copies():
  """One rotary key rotated once and broadcast == the key copied to every
  head and each copy rotated: in the reference written both ways, and in the
  layer against the second. And it IS shared: a change to one rotary column
  of ``W_dkv`` moves every head's scores, a change to one head's own key
  columns of ``W_ukv`` moves that head's alone."""
  shapes = la.LatentShapes(20, 12, 8, 6, 4, 10, 1e-5, 1e4)
  p, h, starts = _case(shapes, HIDDEN, 24)
  rcfg = _rcfg(shapes)
  with jax.default_matmul_precision("highest"):
    shared = ref.attention(rcfg, p, h, starts)
    copied = ref.attention(rcfg, p, h, starts, shared_key=False)
    ours = _ours(shapes, p, h, starts)
  scale = float(jnp.max(jnp.abs(copied)))
  np.testing.assert_allclose(shared, copied, atol=1e-6 * scale)
  np.testing.assert_allclose(ours, copied, atol=2e-5 * scale)

  def heads_out(p):
    """The heads' outputs before ``W_o``: ``[B, S, heads, v]``."""
    eye = dict(p, w_o=jnp.eye(shapes.heads * shapes.v))
    return _ours(shapes, eye, h, starts).reshape(2, 24, shapes.heads, shapes.v)

  base = heads_out(p)
  rotary_col = shapes.kv_rank + 1
  moved = heads_out(dict(p, w_dkv=p["w_dkv"].at[:, rotary_col].add(0.5)))
  per_head = jnp.max(jnp.abs(moved - base), axis=(0, 1, 3))
  assert np.all(np.asarray(per_head) > 1e-4)              # all twenty
  own = 3 * (shapes.nope + shapes.v) + 1                  # head 3's k_n
  moved = heads_out(dict(p, w_ukv=p["w_ukv"].at[:, own].add(0.5)))
  per_head = np.asarray(jnp.max(jnp.abs(moved - base), axis=(0, 1, 3)))
  assert per_head[3] > 1e-4 and np.all(np.delete(per_head, 3) == 0)


def test_rope_turns_the_rotary_parts_alone():
  """With the rotary columns of ``W_uq`` and ``W_dkv`` zeroed the layer does
  not depend on where a token stands: a document alone reads what it reads
  at the end of a packed sequence, to the bit of an XLA tile."""
  p, h, starts = _case(TOY, HIDDEN, 24, batch=1, starts_at=(16,))
  q_cols = np.arange(TOY.heads * (TOY.nope + TOY.rope)).reshape(
      TOY.heads, -1)[:, TOY.nope:].reshape(-1)
  still = dict(p, w_uq=p["w_uq"].at[:, q_cols].set(0.0),
               w_dkv=p["w_dkv"].at[:, TOY.kv_rank:].set(0.0))
  with jax.default_matmul_precision("highest"):
    packed = _ours(TOY, still, h, starts)
    alone = _ours(TOY, still, h[:, 16:], starts[:, :8].at[:, 0].set(True))
    turned = _ours(TOY, p, h, starts)
  np.testing.assert_allclose(packed[:, 16:], alone, atol=1e-6)
  assert float(jnp.max(jnp.abs(turned - packed))) > 1e-3


# ---- the published heads of 256 ---------------------------------------------
WIDE = la.LatentShapes(heads=2, q_rank=24, kv_rank=16, nope=192, rope=64,
                       v=256, eps=1e-5, theta=1e6)


def test_heads_of_256_through_both_attention_paths():
  """The kernel the TPU runs, in Pallas's interpreter (multi-head layout,
  segment ids), against the XLA tile loop given the same ``q``, ``k``, ``v``
  rounded to bfloat16: values and every gradient of the whole layer."""
  p, h, starts = _case(WIDE, 48, 256, batch=1, starts_at=(37, 130),
                       scale=0.15)
  rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
  seen = []

  def tiled(q, k, v, mask, seg):
    seen.append((q.shape, k.shape, v.shape))
    return attention_xla(rounded(q), rounded(k), rounded(v), mask, seg, 64)

  splash = functools.partial(attention_splash, block=128, interpret=True)
  loss = lambda attend: (lambda p, h: jnp.sum(jnp.sin(
      _ours(WIDE, p, h, starts, attend))))
  got = jax.jit(jax.value_and_grad(loss(splash), argnums=(0, 1))).lower(
      p, h).compile()(p, h)
  with jax.default_matmul_precision("highest"):
    want = jax.value_and_grad(loss(tiled), argnums=(0, 1))(p, h)
  assert seen[0] == ((1, 256, 2, 256),) * 3
  assert float(got[0]) == pytest.approx(float(want[0]), rel=3e-3)
  for name, w in {**want[1][0], "h": want[1][1]}.items():
    g = got[1][1] if name == "h" else got[1][0][name]
    assert float(jnp.max(jnp.abs(g - w))) \
        < 0.03 * float(jnp.max(jnp.abs(w))), name


def test_twenty_heads_of_256_lower_for_the_tpu():
  """Pallas -> Mosaic lowering of the multi-head kernel's forward and both
  backward kernels at the published 20 heads of 256, blocks of 512, with the
  documents as segment ids (no chip: Mosaic's own compile is the chip's, and
  `tools/step_recompute.py glm_mla_train_1chip`'s)."""
  rng = np.random.default_rng(0)
  q, k, v = (jnp.asarray(rng.normal(size=(1, 2048, 20, 256)), jnp.float32)
             for _ in range(3))
  seg = jnp.asarray((np.arange(2048) >= 700)[None].astype(np.int32))
  f = jax.grad(lambda q, k, v: jnp.sum(attention_splash(
      q, k, v, Causal(), seg, 512)), argnums=(0, 1, 2))
  text = jax.jit(f).trace(q, k, v).lower(
      lowering_platforms=("tpu",)).as_text()
  for part in ("fwd", "dq", "dkv"):
    assert f"splash_mha_{part}" in text
  assert text.count("tpu_custom_call") >= 3
