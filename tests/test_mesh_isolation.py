"""``conftest.py`` clears the hybrid mesh after every test, so that a file
which sets one cannot decide how the next file on its xdist worker compiles
(``--dist loadfile`` puts whole files on one worker, in turn)."""

import jax

from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                             get_hybrid_mesh,
                                             set_hybrid_mesh)


def test_a_test_sets_a_mesh_and_returns():
    set_hybrid_mesh(create_hybrid_mesh(dp=2, mp=4, devices=jax.devices()))
    assert get_hybrid_mesh() is not None


def test_the_next_test_sees_no_mesh():
    assert get_hybrid_mesh() is None
