"""Checkpoint subsystem tests: Orbax round trip + per-stage restore."""

import jax
import numpy as np
import pytest

from llm_sharding_demo_tpu.models import gpt2
from llm_sharding_demo_tpu.parallel import partition as P_
from llm_sharding_demo_tpu.utils import checkpoint as ckpt


@pytest.fixture(scope="module")
def model():
    config = gpt2.GPT2Config(vocab_size=64, n_positions=16, n_embd=8,
                             n_layer=4, n_head=2)
    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    return config, params


def test_save_load_roundtrip(model, tmp_path):
    config, params = model
    d = str(tmp_path / "ckpt")
    ckpt.save(d, params, config)
    config2, params2 = ckpt.load(d)
    assert config2 == config
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_load_stage_params(model, tmp_path):
    config, params = model
    d = str(tmp_path / "ckpt")
    ckpt.save(d, params, config)
    specs = P_.make_stage_specs(config.n_layer, [2])
    cfg_a, stage_a = ckpt.load_stage_params(d, specs[0])
    assert cfg_a == config
    assert set(stage_a) == {"blocks", "wte", "wpe"}
    assert stage_a["blocks"]["ln_1"]["scale"].shape[0] == 2
    _, stage_b = ckpt.load_stage_params(d, specs[1])
    assert set(stage_b) == {"blocks", "ln_f", "wte_out"}


def test_checkpoint_feeds_forward(model, tmp_path):
    """Restored params produce identical logits."""
    config, params = model
    d = str(tmp_path / "ckpt")
    ckpt.save(d, params, config)
    _, params2 = ckpt.load(d)
    ids = np.random.default_rng(0).integers(0, config.vocab_size, (1, 7))
    a = gpt2.forward(params, ids, config)
    b = gpt2.forward(params2, ids, config)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_state_resume_matches_uninterrupted(model, tmp_path):
    """2 steps -> save -> restore into a fresh process-equivalent -> 2
    more steps == 4 uninterrupted steps. Adam moments and the step
    counter are part of the trajectory; params-only restarts would
    diverge immediately."""
    import jax.numpy as jnp

    from llm_sharding_demo_tpu.training import train

    config, params = model
    ids = np.random.default_rng(7).integers(
        0, config.vocab_size, size=(4, 10))

    step_fn = train.TrainStep(config, train.adamw(1e-2))
    p_ref, s_ref = step_fn.init(params)
    for _ in range(4):
        p_ref, s_ref, _ = step_fn(p_ref, s_ref, jnp.asarray(ids))

    p, s = step_fn.init(params)
    for _ in range(2):
        p, s, _ = step_fn(p, s, jnp.asarray(ids))
    ckpt.save_train_state(str(tmp_path / "t"), p, s, step=2)

    fresh = train.TrainStep(config, train.adamw(1e-2))
    pt, st = fresh.init(params)  # templates with the right structure
    p2, s2, step = ckpt.load_train_state(str(tmp_path / "t"), pt, st)
    assert step == 2
    for _ in range(2):
        p2, s2, _ = fresh(p2, s2, jnp.asarray(ids))

    np.testing.assert_allclose(
        np.asarray(p2["blocks"]["mlp"]["c_fc"]["kernel"]),
        np.asarray(p_ref["blocks"]["mlp"]["c_fc"]["kernel"]),
        atol=1e-6, rtol=1e-6)


def test_stage_partial_restore_matches_slice(model, tmp_path):
    """Per-layer partial restore ≡ full-restore-then-slice, value-exact."""
    config, params = model
    d = str(tmp_path / "ckpt")
    ckpt.save(d, params, config)
    specs = P_.make_stage_specs(config.n_layer, [1, 3])
    for spec in specs:
        _, got = ckpt.load_stage_params(d, spec)
        want = P_.extract_stage_params(params, spec)
        assert jax.tree_util.tree_structure(got) == \
            jax.tree_util.tree_structure(want)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_legacy_stacked_checkpoint_still_loads(model, tmp_path):
    """Checkpoints written before the per-layer layout (stacked [L,...]
    block leaves on disk) load and stage-restore via the fallback path."""
    import dataclasses
    import json

    import orbax.checkpoint as ocp

    config, params = model
    d = tmp_path / "legacy"
    d.mkdir()
    with open(d / "config.json", "w") as f:
        json.dump({"family": "gpt2", **dataclasses.asdict(config)}, f)
    # the old writer: the in-memory stacked tree straight to disk
    ocp.PyTreeCheckpointer().save(str(d / "params"), params, force=True)

    cfg2, params2 = ckpt.load(str(d))
    assert cfg2 == config
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spec = P_.make_stage_specs(config.n_layer, [2])[1]
    _, stage = ckpt.load_stage_params(str(d), spec)
    want = P_.extract_stage_params(params, spec)
    for a, b in zip(jax.tree_util.tree_leaves(stage),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- every family's checkpoint restores as its own config class -----------
# (before there was a family registry the tag knew ``moe`` and ``llama``
# and called everything else ``gpt2``: a latent, linear-attention,
# window, state-space or delta-rule checkpoint then crashed in
# ``GPT2Config(**fields)``)

def _tiny_configs():
    from test_family import TINY
    return sorted(TINY.items())


@pytest.mark.parametrize("name,config", _tiny_configs(),
                         ids=[n for n, _ in _tiny_configs()])
def test_a_checkpoint_of_every_family_restores_its_config(name, config,
                                                          tmp_path):
    from llm_sharding_demo_tpu.models import family_of
    import json
    family = family_of(config)
    params = family.module.init_params(config, jax.random.PRNGKey(0))
    d = str(tmp_path / name)
    ckpt.save(d, params, config)
    with open(tmp_path / name / ckpt.CONFIG_FILE) as f:
        assert json.load(f)["family"] == family.name == name
    restored = ckpt.load_config(d)
    assert type(restored) is type(config) and restored == config
    assert hash(restored) == hash(config)      # tuples came back tuples
    # and the weights, whether the family stacks ``blocks`` or lays its
    # layers out in periods or groups
    _, params2 = ckpt.load(d)
    assert jax.tree.structure(params2) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("tag,wants", [
    (None, "GPT2Config"), ("gpt2", "GPT2Config"), ("gpt3", ValueError)])
def test_what_a_tag_loads_as(model, tmp_path, tag, wants):
    """A directory without a tag is a pre-tag, dense GPT-2 checkpoint;
    an unknown tag is refused by name."""
    import dataclasses
    import json
    import os
    config, _ = model
    fields = dataclasses.asdict(config)
    if tag is not None:
        fields["family"] = tag
    with open(os.path.join(tmp_path, ckpt.CONFIG_FILE), "w") as f:
        json.dump(fields, f)
    if wants is ValueError:
        with pytest.raises(ValueError,
                           match="unknown checkpoint model family 'gpt3'"):
            ckpt.load_config(str(tmp_path))
    else:
        restored = ckpt.load_config(str(tmp_path))
        assert type(restored).__name__ == wants and restored == config
