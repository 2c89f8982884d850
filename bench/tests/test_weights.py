"""The weight draw: the reference's per-layer redraw gives the program's
values bit for bit."""
import numpy as np

from weights import draw_leaf, draw_params


def _cfg():
    from repro.configs import get_config

    return get_config("qwen3-4b-reduced")


def test_layer_redraw_matches_the_whole_draw():
    cfg = _cfg()
    seed = 2**31 + 6
    params = draw_params(cfg, seed)
    wq = params["blocks"]["layer0"]["mixer"]["wq"]
    for layer in (0, cfg.num_layers - 1):
        one = draw_leaf(seed, "['blocks']['layer0']['mixer']['wq']",
                        wq.shape[1:], layer)
        np.testing.assert_array_equal(np.asarray(one, np.float32),
                                      np.asarray(wq[layer], np.float32))
    tok = draw_leaf(seed, "['embed']['tok']", params["embed"]["tok"].shape)
    np.testing.assert_array_equal(np.asarray(tok, np.float32),
                                  np.asarray(params["embed"]["tok"],
                                             np.float32))
