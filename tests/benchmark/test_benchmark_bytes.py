"""The byte model against numbers worked by hand."""

import json
import os

import pytest

from benchmark.harness import bytes as bytes_mod
from benchmark.harness.spec import ROOT


def config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_mistral_l16_by_hand():
    m = bytes_mod.llama(config("mistral-7b-l16"), 2)
    # a layer: q 4096x4096, k and v 4096x1024, o 4096x4096, gate/up/down
    # 3 x 4096x14336, two norms of 4096
    layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256 + 8192
    assert layer == 218_112_000
    head = 4096 * 32768 + 4096
    assert m["weights"] == (16 * layer + head) * 2 == 7_248_027_648
    # 16 layers x (K and V) x 8 heads x 128 x 2 bytes
    assert m["kv_per_token"] == 65_536


def test_gpt2_large_by_hand():
    # GPT-2 large's published sizes (no cell yet: PERF.md, section 7)
    m = bytes_mod.gpt2({"n_embd": 1280, "n_layer": 36, "n_head": 20,
                        "n_positions": 1024, "vocab_size": 50257}, 2)
    d = 1280
    layer = 12 * d * d + 13 * d        # 3dd+dd+4dd+4dd, biases 3d+d+4d+d, LNs 4d
    assert layer == 19_677_440
    assert m["weights"] == (36 * layer + d * 50257 + 2 * d) * 2 == 1_545_438_720
    assert m["kv_per_token"] == 36 * 2 * 1280 * 2 == 184_320


def test_step_bytes_adds_the_live_cache():
    m = {"weights": 1000, "kv_per_token": 10}
    assert bytes_mod.step_bytes(m, 0) == 1000
    assert bytes_mod.step_bytes(m, 250.5) == pytest.approx(3505.0)
