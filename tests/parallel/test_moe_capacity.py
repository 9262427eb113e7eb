"""The receive-capacity ladder of the dropless expert-parallel MoE layer.

``receive_capacities`` gives the static row capacities the dispatch may
receive into, smallest first and ending at the worst case;
``receive_rung`` picks the smallest that holds the most rows any rank
receives, from the all-gathered counts.  Pure functions of shapes and
counts, checked here on the host; the layer's use of them is checked in
``tests/models/test_moonlight.py``.
"""

import numpy as np
import pytest

from mpi4jax_tpu.parallel.moe import (receive_capacities, receive_rung,
                                      receive_rungs)


def _every(receive, size=4, local=2):
    """Counts ``(src, dest, local expert)`` under which destination rank
    ``d`` receives ``receive[d]`` rows, spread over the sources."""
    every = np.zeros((size, size, local), np.int32)
    for dest, rows in enumerate(receive):
        for src in range(size):
            every[src, dest, src % local] = (rows // size
                                             + (src < rows % size))
    assert (every.sum(axis=(0, 2)) == receive).all()
    return every


def test_ladder_at_the_cell_shape():
    """8192 tokens a rank, top-6, four ranks: 1.25, 1.5 and 2 times the
    49152 rows a rank routes, then the worst case; every rung is a
    multiple of the expert kernels' 512-row tile."""
    caps = receive_capacities(8192, 6, 4)
    assert caps == (61440, 73728, 98304, 196608)
    assert all(c % 512 == 0 for c in caps)


@pytest.mark.parametrize("t, top_k, size", [(8192, 6, 1), (16, 2, 2),
                                            (32, 6, 2)])
def test_ladder_collapses_to_the_worst_case(t, top_k, size):
    """One rank, or a worst case no rung rounded to 512 rows stays under:
    the worst case alone."""
    assert receive_capacities(t, top_k, size) == (size * t * top_k,)


def test_rungs_rounded_up_to_whole_tiles():
    """64 tokens top-6 on four ranks (384 rows a rank): 1.25 x 384 and
    1.5 x and 2 x 384 round up to 512 and 1024; the duplicate goes."""
    assert receive_capacities(64, 6, 4) == (512, 1024, 1536)


@pytest.mark.parametrize("rung", [0, 1, 2])
def test_most_received_exactly_at_a_rung_takes_it(rung):
    caps = receive_capacities(8192, 6, 4)
    most = caps[rung]
    receive = [most, 49152, 49152, 4 * 49152 - most - 2 * 49152]
    assert int(receive_rung(_every(receive), caps)) == rung
    receive = [most + 1, 49152, 49152, 4 * 49152 - most - 1 - 2 * 49152]
    assert int(receive_rung(_every(receive), caps)) == rung + 1


def test_even_routing_takes_the_smallest_rung():
    caps = receive_capacities(8192, 6, 4)
    assert int(receive_rung(_every([49152] * 4), caps)) == 0


def test_all_rows_to_one_rank_take_the_worst_case():
    caps = receive_capacities(8192, 6, 4)
    every = _every([0, 0, 4 * 49152, 0])
    assert int(receive_rung(every, caps)) == len(caps) - 1


def test_receive_rungs_reads_each_layer_of_the_prefill_counts():
    """``counts[r, l, e]`` as the prefill returns them: rank ``r`` routed
    to expert ``e`` in layer ``l``.  Each layer maps to the rung
    ``receive_rung`` gives its all-gathered counts."""
    size, local, t, top_k = 4, 2, 8192, 6
    layers = [[49152] * 4, [61444, 49152, 49152, 36860],
              [0, 0, 4 * 49152, 0], [98304, 49152, 49152, 0]]
    counts = np.stack([_every(r, size, local).reshape(size, size * local)
                       for r in layers], axis=1)
    assert counts.shape == (size, len(layers), size * local)
    assert (counts.sum(axis=2) == t * top_k).all()
    np.testing.assert_array_equal(receive_rungs(counts, top_k),
                                  [0, 1, 3, 2])
