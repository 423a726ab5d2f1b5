"""`tools/attn_block_load.py` on the benchmark's own cells (shapes and counts
alone, so the real configurations run on the CPU in seconds): which attention
calls a model makes, and `attn_live_block_share` of a seeded pool against a
count made here from the documents' starts, block by block."""

import importlib.util
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = 512


@pytest.fixture(scope="module")
def tool():
  spec = importlib.util.spec_from_file_location(
      "attn_block_load", os.path.join(ROOT, "tools", "attn_block_load.py"))
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


def _causal_share(starts, window_blocks=None):
  """Live share of a causal mask's blocks of 512 (of the two a row of a
  window of 512 keeps, where ``window_blocks``) counted from one sequence's
  document starts ``[L]`` bool: a block pair lives where some query of one
  and some key of the other, the key not after the query, are one
  document's."""
  seg = np.cumsum(starts) - 1
  n = len(seg) // BLOCK
  first, last = seg[::BLOCK], seg[BLOCK - 1::BLOCK]
  live = total = 0
  for i in range(n):
    for j in range(i + 1):
      if window_blocks and i - j >= window_blocks:
        continue
      total += 1
      live += bool(last[j] >= first[i])     # j <= i: the other test holds
  return live / total


@pytest.mark.parametrize("cell,seed,kinds", [
    # (mask, calls traced, the share the mix gives +- 0.08)
    ("lfm2_moe_train_1chip", 5, [("Causal()", 1, 0.44)]),
    ("glm_mla_train_1chip", 5, [("Causal()", 6, 0.66)]),
    ("laguna_moe_train_1chip", 7, [("Causal()", 2, 0.66),
                                   ("Window(window=512)", 3, 1.0)]),
    ("olmo_hybrid_train_1chip", 5, [("Causal()", 1, 0.50)]),
])
def test_the_live_share_of_a_seeded_pool(tool, capsys, cell, seed, kinds):
  report = tool.main([cell, "--seed", str(seed)])
  assert json.loads(capsys.readouterr().out.splitlines()[-1]) == report
  assert report["backend"] == "cpu" and report["pool_batches"] == 16
  assert [(k["mask"], k["calls_traced"]) for k in report["layers"]] \
      == [kind[:2] for kind in kinds]
  from benchmark import specs, traffic
  c = specs.load_cell(cell)
  spec = c.family().model_spec(c.config)
  pool = traffic.make_pool(c.traffic, spec.inputs, spec.n_numerical, seed,
                           traffic.family_labels(c.family(), c.config))
  mean_length = c.config["mean_document_length"]
  for kind, (mask, _, expected) in zip(report["layers"], kinds):
    assert kind["segment_ids"] and "planned_ms" not in kind
    want = [_causal_share(b.numerical[0] < 1.0 / mean_length,
                          2 if mask.startswith("Window") else None)
            for b in pool]
    assert kind["by_batch"] == pytest.approx(want, abs=1e-4)
    shares = kind["attn_live_block_share"]
    assert shares["fwd"] == shares["dq"] == shares["dkv"] \
        == pytest.approx(np.mean(want), abs=1e-4)
    assert abs(shares["fwd"] - expected) <= 0.08
    if expected < 1.0:
      assert min(kind["by_batch"]) < expected < max(kind["by_batch"]) <= 1.0


def test_no_segment_ids_no_plan(tool):
  """SDAR hands the kernel no documents: every block the mask keeps lives."""
  report = tool.main(["sdar_moe_train_1chip", "--seed", "5"])
  (kind,) = report["layers"]
  assert kind["mask"] == "BlockDiffusion(block_length=4)"
  assert not kind["segment_ids"] and "by_batch" not in kind
  assert set(kind["attn_live_block_share"].values()) == {1.0}


def test_a_model_with_kernels_of_its_own_is_refused(tool):
  with pytest.raises(SystemExit, match="keye_sparse"):
    tool.main(["keye_dsa_train_1chip"])
