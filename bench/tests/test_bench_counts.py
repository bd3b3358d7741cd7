"""The FLOP and byte counters against hand counts at the paper's N=6."""
from bench import counts

HAN = {"hidden": 64, "heads": 4, "layers": 2, "leaky_slope": 0.2}


def test_han_forward_flops_by_hand():
    # N=6 experts, R=W=5 slots each: 30 run and 30 wait request nodes
    d = 64
    proj = 2 * (6 * 9 * d + 60 * 7 * d + 7 * d)
    # node-level attention, expert <- 30 run requests: projections of the
    # 6 targets and 30 neighbours, 6 + 30 scores, 30 weighted rows
    e_run = 2 * (6 + 30) * d * d + 2 * 6 * d + 2 * 30 * d + 2 * 30 * d
    e_wait = e_run
    e_self = 2 * 6 * d * d
    e_sem = 2 * 6 * 3 * d * d + 2 * 6 * 3 * d + 2 * 6 * 3 * d
    a_exp = 2 * (1 + 6) * d * d + 2 * d + 2 * 6 * d + 2 * 6 * d
    a_self = 2 * d * d
    a_sem = 2 * 2 * d * d + 2 * 2 * d + 2 * 2 * d
    requests = 2 * 6 * d * d + 2 * 60 * d * d
    layer1 = e_run + e_wait + e_self + e_sem + a_exp + a_self + a_sem + requests
    layer2 = a_exp + a_self + a_sem   # only the arrived node reaches the output
    assert counts.han_forward_flops(6, 30, 30, HAN) == proj + layer1 + layer2


def test_policy_and_training_flops_by_hand():
    h = counts.han_forward_flops(6, 30, 30, HAN)
    head = 2 * 64 * 64 + 2 * 64 * 7
    assert counts.policy_forward_flops(6, 30, 30, HAN, 64, 7) == h + head
    # 16 envs x 8 collect steps; 8 updates of 256 samples, each 4 HAN and 6
    # head forwards plus the backward of critic (HAN + 2 heads) and actor
    # (HAN + 1 head) at twice their forward
    per_sample = 4 * h + 6 * head + 2 * (2 * h + 3 * head)
    want = 16 * 8 * (h + head) + 8 * 256 * per_sample
    assert counts.train_iteration_flops(6, 30, 30, HAN, 64, 7, 16, 8, 8,
                                        256) == want


def test_advance_bytes_and_ops_by_hand():
    # one fleet of N=6, R=W=5: run planes 5 int + 5 float channels, wait
    # planes 4 + 4, 4 bytes each, read and written; 8-channel parameter
    # pack read; clocks read and written; 6 accumulators written
    queues = 6 * (5 * 10 + 5 * 8) * 4
    one = 2 * queues + 6 * 8 * 4 + 2 * 6 * 4 + 6 * 6 * 4
    assert counts.advance_bytes(6, 5, 5, 1) == one
    assert counts.advance_bytes(6, 5, 5, 16) == 16 * one
    assert counts.advance_ops(6, 5, 5, 16) == 16 * 6 * 90
