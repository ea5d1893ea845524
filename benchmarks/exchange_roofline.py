"""The chip-to-chip peak and the least bytes of the routed read
exchange (the multi-node cells).

Peak: Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of
inter-chip interconnect per chip.  A ``device_kind`` missing from the
table is an error, never a default.

Bytes: what the semantics of a routed read need, whatever implements
it: each unique row whose leaf lives on another chip sends its 8 B key
out and takes its answer back (a found flag of 1 B and the 8 B value).
Shipping whole 1 KB pages, as the exchange does today, reads far below
the roof; a change that ships answers instead cannot push it past 100 %.
"""

from __future__ import annotations

ICI_PEAKS = {
    # device_kind: (ICI bytes/s per chip, source)
    "TPU v5 lite": (1600e9 / 8, "Google Cloud docs, TPU v5e"),
    "TPU v5e": (1600e9 / 8, "Google Cloud docs, TPU v5e"),
}

KEY_BYTES = 8                # the key a remote row sends to its owner
ANSWER_BYTES = 1 + 8         # found flag and value it takes back


class UnknownDeviceError(LookupError):
    pass


def ici_bytes_s(device_kind: str) -> float:
    try:
        return ICI_PEAKS[device_kind][0]
    except KeyError:
        raise UnknownDeviceError(
            f"no ICI peak for device kind {device_kind!r}; add it to "
            "benchmarks/exchange_roofline.py with its source") from None


def routed_read_bytes(remote_rows: float) -> float:
    """Least ICI bytes a chip moves a step for ``remote_rows`` unique
    rows whose leaf lives on another chip."""
    return remote_rows * (KEY_BYTES + ANSWER_BYTES)


def share(bytes_moved: float, exchange_s: float, device_kind: str) -> float:
    """Percent of the ICI roof: bytes over exchange time over peak."""
    return 100.0 * bytes_moved / exchange_s / ici_bytes_s(device_kind)
