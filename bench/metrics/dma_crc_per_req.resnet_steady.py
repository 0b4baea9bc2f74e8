"""CRC-checked DMA payloads (each a blocking device-to-host read) per
request replied to in the window, from the RHAL driver's counters."""
from harness.stats import delta, done_in


def read(run):
    crc = delta(run, "dma_crc_checked")
    n = len(done_in(run.all_records, *run.window))
    return crc / n if crc is not None and n else None
