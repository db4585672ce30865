"""MiniCPM-2B's module against hand counts: the wire of both
configurations, the FLOPs ``mfu`` credits, and the CPU test cut."""
from bench import harness, reference, work
from bench.tests import tiny

C1 = "minicpm-2b.l2.sync-c1-s2048"
C2 = "minicpm-2b.l2.v8.sync-c2-s512"


def _cell(name):
    return harness.load_cell(name)


def test_minicpm_l2_parameter_count():
    c = _cell(C1)
    wire = reference.wire_of(c.model, c.cfg)
    # 122,880 padded rows x 2304 + 2 x (4 x 2304^2 + 3 x 2304 x 5760
    # + 2 x 2304) + 2304
    assert wire.total == 405_220_608
    assert (wire.rows, wire.cols) == (395_724, 1024)
    # the unpadded head: 122,753 x 2304, plus the layers' matrices
    assert c.model.matmul_params(c.cfg) == 122_753 * 2304 + 2 * (
        4 * 2304 ** 2 + 3 * 2304 * 5760)


def test_vocab_slice_parameter_count():
    c = _cell(C2)
    wire = reference.wire_of(c.model, c.cfg)
    assert wire.total == 15_360 * 2304 + 2 * (
        4 * 2304 ** 2 + 3 * 2304 * 5760 + 2 * 2304) + 2304


def test_train_flops_per_token():
    """6 per matrix parameter and causal attention's 6 x L x seq x D;
    a round of c1 (J = tau = 5) makes 6 passes over 2048 tokens."""
    c = _cell(C1)
    per_token = 6 * c.model.matmul_params(c.cfg) + 6 * 2 * 2048 * 2304
    assert c.model.train_flops_per_token(c.cfg, 2048) == per_token
    assert work.round_flops(per_token, c.traffic) == 6 * 2048 * per_token


def test_tiny_cut():
    """Widths 128, heads 4 of 32, SwiGLU 256; at most 500 vocabulary
    rows, 250 where the file holds a slice of the vocabulary."""
    for name, vocab in ((C1, 500), (C2, 250)):
        c = tiny.cell(name)
        assert c.cfg["hidden_size"] == 128 and c.cfg["vocab_size"] == vocab
        assert c.model.Model.from_config(c.cfg).hd == 32
