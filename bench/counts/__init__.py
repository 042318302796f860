"""Operation and byte counts of what the algorithm needs, from shapes:
per SLA call (`sla.py`), per kernel call (`kernels.py`) and per model
step (`models.py`). 1 multiply-accumulate = 2 FLOPs."""
