"""Share (%) of the chip's roofline the ``ssd_scan`` kernel ran at in the
traced window: its launches' roofline time (``kernel_work.py``) over
its device op's seconds."""

import kernel_work


def read(run):
    return kernel_work.roofline_share(run, "ssd_scan")
