"""Substrate coverage: data pipeline and optimizers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypo_compat import given, settings, st

from repro.data import synthetic
from repro.optim.optimizers import make_optimizer


# -- data -------------------------------------------------------------------

def test_token_stream_deterministic_and_seekable():
    s = synthetic.TokenStream(vocab=101, seed=3)
    a = s.round_batch(7, (1, 2, 2, 3), 16)
    b = s.round_batch(7, (1, 2, 2, 3), 16)
    c = s.round_batch(8, (1, 2, 2, 3), 16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert a.shape == (1, 2, 2, 3, 16)
    assert int(a.max()) < 101 and int(a.min()) >= 0


def test_label_partition_is_disjoint_cover():
    _, y = synthetic.gaussian_mixture_task(n_classes=10, n_per_class=20)
    parts = synthetic.label_partition(y, 10)
    all_idx = np.concatenate(parts)
    assert len(all_idx) == len(np.unique(all_idx)) == y.shape[0]
    # each client sees exactly one label
    for p in parts:
        assert len(np.unique(np.asarray(y)[p])) == 1


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=2, max_value=16),
       st.floats(min_value=0.05, max_value=10.0))
def test_dirichlet_partition_cover(n_clients, alpha):
    _, y = synthetic.gaussian_mixture_task(n_classes=6, n_per_class=30)
    parts = synthetic.dirichlet_partition(y, n_clients, alpha=alpha)
    all_idx = np.concatenate([p for p in parts if len(p)])
    assert len(all_idx) == len(np.unique(all_idx)) == y.shape[0]


# -- optimizers ---------------------------------------------------------------

@pytest.mark.parametrize("name,kw", [("sgd", {}), ("momentum", {"beta": 0.9}),
                                     ("adam", {})])
def test_optimizers_descend_quadratic(name, kw):
    opt = make_optimizer(name, lr=0.1, **kw)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}  # d/dw ||w||^2
        params, state = opt.update(grads, state, params)
    assert float(jnp.linalg.norm(params["w"])) < 1e-2
