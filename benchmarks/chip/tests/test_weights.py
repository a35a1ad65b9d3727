"""The served program gets exactly the weights the reference regenerates
layer by layer: one jitted call for the program, one per layer for the
reference."""
import jax
import numpy as np

from benchmarks.chip.adapters import dense_lm as adapter
from benchmarks.chip.reference import dense_lm as ref
from benchmarks.chip.tests import tiny


def test_program_tree_matches_reference_layers():
    cfg = tiny.TINY_LM
    kd = ref.key_data(2**31 + 5)
    p = jax.jit(lambda k: adapter.to_program(ref.all_weights(cfg, k)))(kd)
    fc = ref._freeze(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        w = ref._gen_layer(fc, kd, np.int32(layer))
        np.testing.assert_array_equal(p["layers"]["attn"]["wq"][layer],
                                      w["wq"])
        np.testing.assert_array_equal(p["layers"]["attn"]["bk"][layer],
                                      w["bk"])
        np.testing.assert_array_equal(p["layers"]["ffn"]["wg"][layer],
                                      w["w_gate"])
        np.testing.assert_array_equal(p["layers"]["ln2"][layer], w["ln2"])
    g = ref._gen_globals(fc, kd)
    np.testing.assert_array_equal(p["embed"], g["embed"])


def test_tree_has_the_programs_layout():
    import jax.numpy as jnp
    from benchmarks.chip import common
    common.use_program()
    from repro.models.registry import build_model
    cfg = tiny.TINY_LM
    want = build_model(adapter.model_config(cfg)).abstract_params()
    got = jax.eval_shape(lambda k: adapter.to_program(ref.all_weights(cfg, k)),
                         jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
