"""``moe_gmm_roofline`` against hand counts: the ``moonlight`` family's
grouped-matmul FLOPs and bytes of one training step, and the reader on
small recorded traces."""
import pytest

from bench import harness
from bench.cells import family, reader, resolve
from bench.peaks import chip_peaks
from bench.trace_reduce import Span
from bench.trace_scopes import ScopedOp, ScopedTrace

CELL = "moonlight.cc_power"
FAMILY = family(resolve(CELL))
GMM = "jit(run_span)/fed.local_sgd/while/body/fed.moe/fed.moe_gmm/pallas_call"


def hand(batch, seq, top_k, d, f, experts, moe_layers):
    """9 products a MoE layer a step (3 forward, 3 recomputed, 3 for the
    activation gradients), each R = batch·seq·top_k rows of d × f; bytes:
    every expert's weight once, R rows in and R rows out, 2 bytes each."""
    rows = batch * seq * top_k
    n = 9 * moe_layers
    return n * 2 * rows * d * f, n * 2 * (experts * d * f + rows * (d + f))


@pytest.mark.parametrize("size", ["published", "cpu"])
def test_grouped_matmul_work_of_one_step_by_hand(size):
    config = resolve(CELL).config
    if size == "cpu":
        config = FAMILY.shrink(config)
        # 2 sequences of 16 tokens, 3 experts each, d 64, f 32, 8 experts,
        # 2 MoE layers: R = 96, 18 products
        assert hand(2, 16, 3, 64, 32, 8, 2) == (18 * 393_216, 18 * 51_200)
        want = (7_077_888, 921_600)
    else:
        # R = 2 · 1,024 · 6 = 12,288 rows of 2,048 × 1,408, 6 MoE layers
        want = hand(2, 1024, 6, 2048, 1408, 64, 6)
        assert want[0] == 54 * 70_866_960_384
    assert FAMILY.moe_gmm_work(config) == want


def _run(ops, rounds=16, trained=60, traced=16):
    cell = resolve(CELL)
    rec = harness.RunRecord(config=cell.config, traffic=cell.traffic,
                            chips=1, peaks=chip_peaks("TPU v5 lite"),
                            rounds=rounds, trained_client_rounds=trained,
                            family=FAMILY)
    rec.trace = ScopedTrace(ops=ops, spans=[Span("bench.span", 0, 1e10)])
    rec.traced_rounds, rec.devices = traced, [0]
    return rec


def test_the_reader_divides_the_roofline_by_the_training_gmm_time():
    """Two training grouped matmuls of 1 s each, overlapping for half of
    one, and one of the evaluation's, which is left out: 1.5 s measured."""
    ops = [ScopedOp(0, "gmm.1", 0, 1e9, scope=GMM),
           ScopedOp(0, "gmm.2", 5e8, 1e9, scope=GMM),
           ScopedOp(0, "gmm.3", 3e9, 1e9,
                    scope="jit(hits)/fed.eval/fed.moe/fed.moe_gmm/pallas")]
    got = reader("moe_gmm_roofline")(_run(ops))
    flops, nbytes = FAMILY.moe_gmm_work(resolve(CELL).config)
    steps = 16 * 60 / 16 * 2                       # 120 traced steps
    roof = steps * max(flops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * roof / 1.5)
    # bound by bytes: 170 FLOP a byte, under the chip's 240
    assert 150 < flops / nbytes < 240


def test_the_reader_finds_nothing_without_the_scope():
    ops = [ScopedOp(0, "fusion.1", 0, 1e9,
                    scope="jit(run_span)/fed.local_sgd/while/body/dot")]
    assert reader("moe_gmm_roofline")(_run(ops)) is None
    assert reader("moe_ms_per_round")(_run(ops)) is None
    assert reader("mla_ms_per_round")(_run(ops)) is None


def test_the_layer_readers_take_the_union_of_their_scope():
    ops = [ScopedOp(0, "a", 0, 2e9, scope="x/fed.local_sgd/fed.moe/sort"),
           ScopedOp(0, "b", 1e9, 2e9, scope=GMM),
           ScopedOp(0, "c", 4e9, 1e9, scope="x/fed.eval/fed.mla/dot")]
    assert reader("moe_ms_per_round")(_run(ops)) == pytest.approx(
        1e3 * 3.0 / 16)
    assert reader("mla_ms_per_round")(_run(ops)) == pytest.approx(
        1e3 * 1.0 / 16)
