"""The plain reference's clip search: channels whose two best clip
fractions tie are settled by the served answers, and nothing else is."""

import numpy as np
import pytest

import _paths  # noqa: F401
from chipbench import model, reference
from smoke_cells import smoke_conf


@pytest.fixture(scope="module")
def vgg():
    conf = smoke_conf("vgg16_w4_fused")
    params, images = model.make(conf, 21, 48)
    return conf, params, np.asarray(images)


def _widest_tie(conf, params):
    """The packed channel whose two best fractions lie closest."""
    import jax

    ops = jax.jit(lambda p: reference.prepare(
        conf, p, conf["bits"], reference.no_flips(conf)))(params)
    return min((float(g), name, c) for name, op in ops.items()
               if "tie" in op for c, g in enumerate(np.asarray(op["tie"])))


def test_ties_are_settled_by_the_answers(vgg, monkeypatch):
    """Answers made with the other choice in one near-tied channel are
    matched exactly once that choice is found."""
    conf, params, images = vgg
    gap, name, c = _widest_tie(conf, params)
    monkeypatch.setattr(reference, "TIE_REL", gap * 1.01)
    base, settled = reference.logits(conf, params, images, 4)
    assert settled == []
    flips = reference.no_flips(conf)
    flips[name][c] = True
    import jax

    other = jax.jit(lambda p, f: reference.prepare(conf, p, 4, f))(
        params, flips)
    served = np.asarray(jax.jit(lambda o, x: reference.forward(conf, o, x))(
        other, images))
    if np.array_equal(served, base):
        pytest.skip("the other choice changes no answer of these images")
    ref, settled = reference.logits(conf, params, images, 4, served=served)
    assert settled == [(name, c)]
    np.testing.assert_array_equal(ref, served)


def test_answers_that_are_wrong_stay_wrong(vgg, monkeypatch):
    """Answers of other requests get no closer by settling ties."""
    conf, params, images = vgg
    monkeypatch.setattr(reference, "TIE_REL", 1e-1)
    base, _ = reference.logits(conf, params, images, 4)
    served = np.roll(base, 1, axis=0)
    ref, settled = reference.logits(conf, params, images, 4, served=served)
    gap = np.sqrt(np.mean((served - ref) ** 2, axis=1))
    assert np.mean(gap > 0.1 * np.sqrt(np.mean(base ** 2))) > 0.9
