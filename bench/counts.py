"""Operations and bytes of the router and the engine, counted from shapes.

FLOPs count the multiply-adds of matrix products as two operations each;
elementwise work (activations, softmax, masks) is left out, so these are
model FLOPs of the HAN and the SAC heads.  Work that does not reach the
output is not counted: the last HAN layer updates only the arrived
request's node, since that embedding is all the actor and critics read.
"""
from __future__ import annotations

# packed queue channels: run_i, run_f (5 each), wait_i, wait_f (4 each),
# 4 bytes an element; the per-expert parameter pack has 8 float32 channels
RUN_CH, WAIT_CH, PAR_CH, ACC_FIELDS, WORD = 10, 8, 8, 6, 4


def han_forward_flops(n: int, run_rows: int, wait_rows: int,
                      han: dict) -> int:
    """One graph's forward: ``n`` expert nodes, ``run_rows``/``wait_rows``
    request nodes (every slot of the padded layout, the live slots of the
    segment layout), to the arrived request's embedding."""
    d, layers = han["hidden"], han["layers"]
    rows = run_rows + wait_rows
    f = 2 * d * (9 * n + 7 * rows + 7)                  # input projections

    def gat(targets, neigh):
        # target and neighbour projections, the two attention scores and
        # the attention-weighted sum (heads split d, so each is d wide)
        return 2 * d * d * (targets + neigh) + 2 * d * (targets + 2 * neigh)

    def semantic(nodes, paths):
        return 2 * nodes * paths * (d * d + 2 * d)

    arrived = gat(1, n) + 2 * d * d + semantic(1, 2)
    full = (gat(n, run_rows) + gat(n, wait_rows) + 2 * n * d * d
            + semantic(n, 3) + arrived
            + 2 * d * d * (n + rows))                   # request nodes
    return f + (layers - 1) * full + arrived


def mlp_flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def policy_forward_flops(n: int, run_rows: int, wait_rows: int, han: dict,
                         sac_hidden: int, n_actions: int) -> int:
    """One routing decision: the HAN and the actor head."""
    return (han_forward_flops(n, run_rows, wait_rows, han)
            + mlp_flops((han["hidden"], sac_hidden, n_actions)))


def train_iteration_flops(n: int, run_rows: int, wait_rows: int, han: dict,
                          sac_hidden: int, n_actions: int, n_envs: int,
                          collect_steps: int, updates: int,
                          batch: int) -> int:
    """One training iteration: the collect's policy forwards, then
    ``updates`` SAC updates of ``batch`` samples.  Per sample the update
    runs four HAN forwards (actor and target critic on the next
    observation, critic and actor on the observation) and six heads, and
    the backward (twice the forward) of the critic and the actor."""
    h = han_forward_flops(n, run_rows, wait_rows, han)
    m = mlp_flops((han["hidden"], sac_hidden, n_actions))
    per_sample = (4 * h + 6 * m) + 2 * ((h + 2 * m) + (h + m))
    collect = n_envs * collect_steps * (h + m)
    return collect + updates * batch * per_sample


def advance_bytes(n: int, r: int, w: int, n_envs: int) -> int:
    """Least HBM traffic of one engine advance of ``n_envs`` fleets: the
    queue planes read and written, the parameter pack read, the clocks
    read and written and the completion accumulators written."""
    queues = n * (r * RUN_CH + w * WAIT_CH) * WORD
    per_env = 2 * queues + n * PAR_CH * WORD + 2 * n * WORD \
        + ACC_FIELDS * n * WORD
    return n_envs * per_env


def advance_ops(n: int, r: int, w: int, n_envs: int) -> int:
    """Least elementwise work of one advance: one pass over every queue
    element of every fleet."""
    return n_envs * n * (r * RUN_CH + w * WAIT_CH)
