"""What the package holds: ``ring_hlo_evidence.py`` (a hand tool: the
collective ops each ring schedule compiles to) and ``trace_lock.json``
(the pinned fingerprints and budgets of ``python -m tpudp.analysis audit``).
"""
