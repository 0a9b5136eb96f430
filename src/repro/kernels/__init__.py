"""Pallas TPU kernels, each with a pure-jnp oracle in ``ref.py``.

``packed_attention`` is on the training path (``models/attention.py``
selects it on a TPU); ``wkv6`` and ``flash_decode`` are reached from the
tests and ``chip_smoke.py``.
"""
