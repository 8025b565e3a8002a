"""Share of the traced step's row-gather bytes that the selector took on the
device, %: the ``bytes`` of the ``selector.rows`` spans whose ``where`` is
``device`` (a ``jnp.take`` from the resident matrix) over the ``bytes`` of
all of them (``host``: numpy's copy).  None where no such span says
``where``, as in a program that gathers every row set with numpy."""
from benchmarks import host_spans, program_spans


def read(r):
    rows = [s[3] for s in program_spans.load(r)["spans"]
            if s[0] == host_spans.ROWS]
    total = sum(st.get("bytes", 0) for st in rows)
    if total <= 0 or not any("where" in st for st in rows):
        return None
    on_device = sum(st.get("bytes", 0) for st in rows
                    if st.get("where") == "device")
    return 100.0 * on_device / total
