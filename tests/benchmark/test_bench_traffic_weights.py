"""The benchmark's own weights and traffic: functions of the seed alone, for
any seed up to 2**63 - 1, the same on the host and on the device."""

import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy  # noqa: F401
from benchmark import traffic, weights

SEEDS = [0, 7, 2**31 + 9001, 2**63 - 1]
INPUTS = (traffic.CatInput(0, 1000, 1), traffic.CatInput(1, 50, 10),
          traffic.CatInput(1, 50, 1))
MIX = {"alpha": 1.05, "global_batch": 64, "pool_batches": 2,
       "numerical_range": [0, 100]}


@pytest.mark.parametrize("seed", SEEDS)
def test_host_and_device_weights_are_the_same_bits(seed):
  key = weights.leaf_key(seed, "table_003")
  rows = np.array([0, 1, 5, 2**24 + 3, 39_979_770])
  host = weights.rows_np(key, 0.05, rows, 16)
  dev = weights.unit_uniform(
      jnp, jnp.uint32(key), jnp.asarray(rows, jnp.uint32)[:, None],
      jnp.arange(16, dtype=jnp.uint32)[None, :]) * jnp.float32(0.05)
  assert host.dtype == np.float32
  assert np.array_equal(host, np.asarray(dev))
  assert np.all(np.abs(host) <= 0.05) and len(np.unique(host)) > 70


def test_keys_differ_by_seed_and_by_leaf():
  keys = {weights.leaf_key(s, n) for s in SEEDS for n in ("a", "b")}
  assert len(keys) == 2 * len(SEEDS)
  with pytest.raises(ValueError):
    weights.leaf_key(-1, "a")


def test_weights_are_spread_over_the_interval():
  w = weights.rows_np(weights.leaf_key(3, "t"), 1.0, np.arange(4096), 32)
  assert abs(float(w.mean())) < 0.01
  assert abs(float(w.std()) - 1 / np.sqrt(3)) < 0.01


@pytest.mark.parametrize("seed", SEEDS)
def test_traffic_is_a_function_of_the_seed(seed):
  a = traffic.make_pool(MIX, INPUTS, 3, seed)
  b = traffic.make_pool(MIX, INPUTS, 3, seed)
  c = traffic.make_pool(MIX, INPUTS, 3, seed + 1 if seed < 2**63 - 1 else 1)
  assert all(np.array_equal(x.cats, y.cats) and
             np.array_equal(x.numerical, y.numerical) for x, y in zip(a, b))
  assert not np.array_equal(a[0].cats, c[0].cats)
  assert not np.array_equal(a[0].cats, a[1].cats)
  batch = a[0]
  assert batch.cats.shape == (64, 12) and batch.cats.dtype == np.int32
  assert batch.numerical.shape == (64, 3) and batch.labels.shape == (64,)
  assert batch.cats[:, 0].max() < 1000 and batch.cats[:, 1:].max() < 50
  assert batch.cats.min() >= 0 and 0 <= batch.numerical.min()
  assert set(np.unique(batch.labels)) <= {0.0, 1.0}


def test_touched_rows_merges_the_inputs_of_a_shared_table():
  batch = traffic.make_batch(MIX, INPUTS, 3, 9, 0)
  touched = traffic.touched_rows(batch, INPUTS)
  assert set(touched) == {0, 1}
  assert np.array_equal(touched[1], np.unique(batch.cats[:, 1:]))
  assert traffic.column_spans(INPUTS) == [(0, 1), (1, 11), (11, 12)]


def test_power_law_is_skewed_and_uniform_is_not():
  rng = np.random.default_rng(0)
  skew = traffic.power_law_ids(rng, 20000, 10**6, 1.05)
  flat = traffic.power_law_ids(rng, 20000, 10**6, 0)
  assert np.mean(skew < 1000) > 0.4 > 0.01 > np.mean(flat < 1000)
