"""The chip's published peaks, keyed by JAX's ``device_kind``
(``bench/peaks.json``).  A device that is not in the table is an error."""
from __future__ import annotations

import functools
import json
import pathlib


@functools.cache
def table() -> dict:
    return json.loads((pathlib.Path(__file__).parent / "peaks.json").read_text())


def lookup(device_kind: str) -> dict:
    try:
        return table()["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table()['devices'])}") from None
