"""XLA profiler capture — the ``tpudp.obs`` home of the old
``tpudp.utils.profiler.trace`` wrapper (that module re-exports from
here, so existing imports keep working).

The host-side recorder (``tpudp/obs/record.py``) answers "what was the
scheduler doing"; THIS layer answers "what was the chip doing": a real
XLA/TPU profile (TensorBoard trace-viewer format) around any region.
While it captures, the recorder's ``begin``/``end`` spans appear in it
as ``tpudp.<recorder>.<span>`` annotations on the host plane (the
recorder finds the session itself; nothing is wired through here).  jax
is imported lazily so ``tpudp.obs`` stays importable from stdlib-only
tooling (the same discipline as ``tpudp.analysis``).
"""

from __future__ import annotations

import contextlib
from typing import Iterator


@contextlib.contextmanager
def trace(log_dir: str | None) -> Iterator[None]:
    """XLA profiler capture into ``log_dir`` (no-op when None).  View
    with TensorBoard's profile plugin or xprof."""
    if log_dir is None:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield
