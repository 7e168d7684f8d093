"""Share of the decoding rows' table entries that the paged decode kernel
walks over the window (kernels layer): the window's change in the
engine's ``stats["decode_pages_walked"]`` over its change in
``stats["decode_table_pages"]``, in %.  A kernel that walked every entry
of every table reads 100."""


def read(w):
    if "engine.decode_pages_walked" not in w.counters1:
        return None
    pages = (w.counters1["engine.decode_table_pages"]
             - w.counters0["engine.decode_table_pages"])
    if pages <= 0:
        return None
    return 100.0 * (w.counters1["engine.decode_pages_walked"]
                    - w.counters0["engine.decode_pages_walked"]) / pages
