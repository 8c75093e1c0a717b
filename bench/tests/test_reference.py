"""What the harness takes from a configuration file and from its reference
module: the program's ``ModelConfig``, and weights drawn by the module's
initialisation rule."""
import hashlib
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import weights
from references import dense

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
REDUCED = dict(num_layers=4, d_model=128, num_heads=4, num_kv_heads=2,
               head_dim=32, d_ff=256, vocab_size=4096)
# sha256 (first 16 hex digits) of every leaf, in the tree's order, of the
# weights drawn from seed 7_000_000_001 at the reduced sizes above (bf16,
# as the configurations serve them). A seed must keep drawing the same
# weights, bit for bit, or the ledger's readings of one seed stop being
# comparable from one version of the harness to the next.
DIGESTS = {
    "qwen2.5-3b": [
        ("embed/embedding", "2f4cd499a9c1b75b"),
        ("final_norm", "40c2f20f58960e57"),
        ("layers/attn/bk", "a799f3851a9fafa9"),
        ("layers/attn/bq", "dac2c2b7a554489b"),
        ("layers/attn/bv", "f2fbe25f7bdbd2bc"),
        ("layers/attn/wk", "12d8c9ec9e070053"),
        ("layers/attn/wo", "263c738efcd1eba5"),
        ("layers/attn/wq", "33fd324f9251cbb5"),
        ("layers/attn/wv", "bd0ed19254372f55"),
        ("layers/ln_attn", "99e02b5c4baa65c8"),
        ("layers/ln_mlp", "16b0be0b976e61b1"),
        ("layers/mlp/wi_gate", "bd83985cb53abd00"),
        ("layers/mlp/wi_up", "938a42317e1df107"),
        ("layers/mlp/wo", "16216e7ec810fc13"),
    ],
    "h2o-danube-1.8b": [
        ("embed/embedding", "2f4cd499a9c1b75b"),
        ("embed/unembedding", "cac19150b083150b"),
        ("final_norm", "d1359b876b4f38ab"),
        ("layers/attn/wk", "e116fbf977f72dfb"),
        ("layers/attn/wo", "e2bfe6bf51b2e9f6"),
        ("layers/attn/wq", "5be8e432f92eeaf9"),
        ("layers/attn/wv", "b909028ace2c1551"),
        ("layers/ln_attn", "80c8dc83dc98c30a"),
        ("layers/ln_mlp", "794a42e5645a99e4"),
        ("layers/mlp/wi_gate", "d4bd089719397245"),
        ("layers/mlp/wi_up", "aa75cafa48ea76dc"),
        ("layers/mlp/wo", "deef36edb38c2085"),
    ],
}


def _digests(params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return [("/".join(str(getattr(k, "key", k)) for k in p),
             hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16])
            for p, x in flat]


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_weights_are_those_of_the_rule_before_it_moved(name):
    from repro.models import get_model
    sizes = dict(json.loads((CONFIGS / f"{name}.json").read_text())["model"],
                 **REDUCED)
    api = get_model(run.model_config(sizes))
    params = weights.make_params(api, sizes, 7_000_000_001, dense)
    assert _digests(params) == DIGESTS[name]


def _api(**leaves):
    return types.SimpleNamespace(init=lambda key: {
        k: jnp.zeros(shape, jnp.bfloat16) for k, shape in leaves.items()})


def test_a_parameter_without_a_rule_is_refused_naming_the_module():
    api = _api(router=(4, 8))
    with pytest.raises(KeyError, match="references.dense.*router"):
        weights.make_params(api, REDUCED, 1, dense)


def test_the_configured_module_supplies_the_rule():
    rule = types.ModuleType("experts_rule")
    rule.init_std = lambda path, shape, cfg: {"router": 0.0,
                                              "experts": 2.0}[path]
    params = weights.make_params(_api(router=(64, 64), experts=(64, 64)),
                                 REDUCED, 1, rule)
    assert not np.any(np.asarray(params["router"], np.float32))
    assert 1.8 < float(jnp.std(params["experts"].astype(jnp.float32))) < 2.2


def test_model_config_takes_every_list_as_a_tuple():
    sizes = dict(json.loads((CONFIGS / "qwen2.5-3b.json").read_text())[
        "model"], window_pattern=[8, 0], block_pattern=["A", "R"])
    cfg = run.model_config(sizes)
    assert cfg.window_pattern == (8, 0) and cfg.block_pattern == ("A", "R")
    hash(cfg)


def test_model_config_refuses_a_key_the_program_lacks():
    sizes = dict(json.loads((CONFIGS / "qwen2.5-3b.json").read_text())[
        "model"], n_shared_experts=2)
    with pytest.raises(TypeError, match="n_shared_experts"):
        run.model_config(sizes)
