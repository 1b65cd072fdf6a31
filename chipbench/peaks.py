"""The chip's published peaks (``peaks.json``, keyed by ``device_kind``)
and the roofline share computed against them."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """A ``device_kind`` the peak table has no row for."""


def load(path: Path = TABLE) -> dict:
    return json.loads(Path(path).read_text())


def device(kind: str, table: dict = None) -> dict:
    """The peaks of one device kind; an unknown kind is an error, never
    a default."""
    table = table or load()
    try:
        return table["devices"][kind]
    except KeyError:
        raise UnknownDevice(f"no peaks for device_kind {kind!r} in "
                            f"{TABLE.name}; known: "
                            f"{sorted(table['devices'])}") from None


def ops_peak(kind: str, dtype: str, table: dict = None) -> float:
    """Operations per second the chip can do in ``dtype``."""
    table = table or load()
    return float(device(kind, table)[table["peak_of_dtype"][dtype]])


def roofline(ops: float, nbytes: float, seconds: float, kind: str,
             dtype: str, table: dict = None) -> Tuple[float, str]:
    """Share (%) of the roofline a kernel reached: the least time the chip
    could take, max(ops / peak, bytes / HBM bandwidth), over the
    kernel's measured time; and which of the two bounds it."""
    table = table or load()
    t_ops = ops / ops_peak(kind, dtype, table)
    t_mem = nbytes / float(device(kind, table)["hbm_bytes_per_s"])
    least = max(t_ops, t_mem)
    return 100.0 * least / seconds, ("compute" if t_ops >= t_mem else "hbm")


def hbm_roofline(nbytes: float, seconds: float, kind: str,
                 table: dict = None) -> float:
    """Share (%) of the roofline of a kernel bound by its bytes alone:
    bytes over the HBM bandwidth, over the kernel's measured time."""
    table = table or load()
    return 100.0 * nbytes / float(device(kind, table)["hbm_bytes_per_s"]) \
        / seconds
