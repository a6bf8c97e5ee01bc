"""Share of its roofline of the ``adc_scan_topl`` kernel, in %
(``_roofline.py``)."""
from chipbench.metrics._roofline import read_kernel


def read(ctx):
    return read_kernel(ctx, "adc_scan_topl")
