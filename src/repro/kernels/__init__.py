# Compute hot-spot kernels (<name>/kernel.py + ops.py + ref.py per op).
