"""The benchmark's own code: everything that decides a number or `correct`.

Nothing here imports the program except `nodes.py` (the launcher, which
needs the program's replica-id hash and native build) and `state.py`
(which hands seed-made batches to the program's snapshot writer so that
the node loads them through its normal boot recovery).
"""
