"""The weight draw: the reference's per-leaf redraw gives the program's
values bit for bit, for a leaf of a period-1 scanned stack, of a dense
prefix layer before the scan, and of a Mamba mixer inside a longer
period, the paths that architecture modules read."""
import dataclasses

import jax
import numpy as np
import pytest

from weights import draw_leaf, draw_params

SEED = 2**31 + 6
CASES = {
    "qwen3-4b-reduced": ({}, ["['blocks']['layer0']['mixer']['wq']",
                              "['embed']['tok']"]),
    # at 3 layers the dense layer 0 goes before a period-1 scan of the
    # two expert layers
    "deepseek-moe-16b-reduced": ({"num_layers": 3}, [
        "['prefix']['layer0']['mlp']['wi_gate']",
        "['prefix']['layer0']['mixer']['wq']"]),
    # attention at index 4 of each period of 8: layer 1 is a Mamba mixer
    "jamba-v0.1-52b-reduced": ({}, [
        "['blocks']['layer1']['mixer']['a_log']",
        "['blocks']['layer6']['mixer']['conv_w']"]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_layer_redraw_matches_the_whole_draw(name):
    from repro.configs import get_config

    changes, paths = CASES[name]
    cfg = dataclasses.replace(get_config(name), **changes)
    params = draw_params(cfg, SEED)
    leaves = {jax.tree_util.keystr(p): a for p, a in
              jax.tree_util.tree_flatten_with_path(params)[0]}
    for path in paths:
        whole = np.asarray(leaves[path], np.float32)
        if not path.startswith("['blocks']"):
            one = draw_leaf(SEED, path, whole.shape)
            np.testing.assert_array_equal(np.asarray(one, np.float32), whole)
            continue
        assert whole.shape[0] >= 2, (path, whole.shape)
        for layer in (0, whole.shape[0] - 1):
            one = draw_leaf(SEED, path, whole.shape[1:], layer)
            np.testing.assert_array_equal(np.asarray(one, np.float32),
                                          whole[layer])
