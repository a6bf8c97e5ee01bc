"""The kernels' work counts against hand counts (CPU only)."""
from chipbench.kernels import adc_scan_topl


def test_flat_scan_counts_each_code_once_a_call():
    # 2 queries over 3 codes of 2 bytes, tables of 4 entries, top-1:
    # 2*3*2 adds; 6 code bytes + 2*2*4*4 table bytes + 2*1*8 output bytes
    assert adc_scan_topl.work(q=2, n=3, m=2, k=4, topl=1) == (12, 6 + 64 + 16)


def test_flat_scan_bytes_do_not_grow_with_queries_but_ops_do():
    one = adc_scan_topl.work(q=1, n=10 ** 7, m=8, k=256, topl=500)
    many = adc_scan_topl.work(q=128, n=10 ** 7, m=8, k=256, topl=500)
    assert many[0] == 128 * one[0]
    assert many[1] - one[1] == 127 * (8 * 256 * 4 + 500 * 8)


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)

    def served_queries(self):
        return self.served


def test_window_work_of_a_flat_scan():
    cfg = {"n_per_chip": 100, "shards": 1, "num_codebooks": 8,
           "codebook_size": 256, "rerank": 10}
    ctx = Ctx(config=cfg, serve={"batches": 3, "real_queries": 20})
    ops, nbytes = adc_scan_topl.window(ctx)
    assert ops == 20 * 100 * 8
    assert nbytes == 3 * 800 + 20 * (8 * 256 * 4 + 10 * 8)
    assert adc_scan_topl.window(Ctx(config=cfg, serve={})) is None
