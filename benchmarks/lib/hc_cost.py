"""What the hyper-connections of a program must move, from its shapes: the
function behind ``hc_roofline`` (``glm-5.3-flash-l5``; the keys are the
catalog's).

Every sub-layer (a layer's mixer, then its feed-forward: two a layer) reads
its ``Phi`` [n D, 2 n + n n] as stored, reads the row's n streams, writes
them back mixed, and hands one [D] row to the sub-layer and takes one from
it. The arithmetic is nothing beside that (a row's projection is n D (2 n +
n n) x 2 = 0.8 MFLOP against 1.6 MB of ``Phi``; twenty sweeps over sixteen
numbers): the part waits for memory where it does not wait for launches.
"""

WEIGHT_BYTES = 4        # float32 storage (PERF.md section 7)
STREAM_BYTES = 2        # the residual's streams in bf16 between sub-layers


def sublayers(cfg):
    """Sub-layers with maps of their own: two a kept layer."""
    return 2 * cfg["num_hidden_layers"]


def phi_bytes(cfg):
    """One sub-layer's ``Phi``, ``alpha`` and ``b`` as stored."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    width = 2 * n + n * n
    return WEIGHT_BYTES * (n * d * width + 3 + width)


def rows_bytes(cfg, rows):
    """What ``rows`` tokens move through one sub-layer's maps and mix: the n
    streams read once and written once, the sub-layer's input written and
    its output read."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    return rows * STREAM_BYTES * d * (2 * n + 2)


def step_bytes(cfg, *, live):
    """Bytes the ``hc.*`` part of one decode step moves: every sub-layer's
    parameters as stored and the live slots' rows through each."""
    return sublayers(cfg) * (phi_bytes(cfg) + rows_bytes(cfg, live))


def block_bytes(cfg, *, rows):
    """The same for a prefill block of ``rows`` positions."""
    return sublayers(cfg) * (phi_bytes(cfg) + rows_bytes(cfg, rows))
