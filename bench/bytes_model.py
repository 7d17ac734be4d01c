"""Bytes a lookup needs, by the Honeycomb paper's Section 3.1 accounting:
at each interior level the header, the shortcut block and one segment of
the sorted block; at the leaf the same plus the log block.

Copied from ``benchmarks/common.py:bytes_model_honeycomb`` and the byte
sizes of ``HoneycombConfig``, taking the node geometry from the
configuration file's ``store`` entry instead of the program."""

HEADER_BYTES = 48


def lookup_bytes(store: dict, height: int) -> int:
    key_bytes = 4 * store["key_words"]
    val_bytes = 4 * store["val_words"]
    shortcut = store["n_shortcuts"] * (key_bytes + 4)
    segment = (store["node_cap"] // store["n_shortcuts"]) \
        * (key_bytes + val_bytes + 4)
    log = store["log_cap"] * (key_bytes + val_bytes + 12)
    per_interior = HEADER_BYTES + shortcut + segment
    return per_interior * (height - 1) + per_interior + log
