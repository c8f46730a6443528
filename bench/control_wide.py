"""``bench/control.py`` at widths where the reference's control loop does
not compile.

    python3 bench/control_wide.py --workload imdb10k-bulk --seconds 3 \
        --seeds 101

``bench/reference.py`` counts the control's votes one at a time into a
``vote_bits``-wide wrapping register, a loop that unrolls M additions:
at M=10,000 (``tm-imdb-10k``) its compile passed 40 GB of host memory.
Wrapping after every vote equals wrapping the exact int32 sum once (both
are the sum mod ``2**bits`` in ``[-2**(bits-1), 2**(bits-1))``), so
:func:`infer` gives the same predictions and sums in closed form, and
this script runs ``bench/control.py`` with it in the reference's place.
The readings are the ones ``bench/control.py`` would give.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from bench import control, reference                          # noqa: E402

_EXACT = reference._infer


@functools.partial(jax.jit, static_argnames=("n_states", "vote_bits"))
def infer(ta, lits, *, n_states, vote_bits):
    """``reference._infer`` with the ``vote_bits`` register wrapped once
    over the exact sums."""
    pred, sums = _EXACT(ta, lits, n_states=n_states, vote_bits=0)
    if vote_bits:
        sums = reference._wrap(sums, vote_bits)
        pred = jnp.argmax(sums, axis=-1).astype(jnp.int32)
    return pred, sums


def main(argv=None) -> int:
    reference._infer = infer
    try:
        return control.main(argv)
    finally:
        reference._infer = _EXACT


if __name__ == "__main__":
    sys.exit(main())
