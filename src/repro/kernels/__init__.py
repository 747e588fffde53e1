"""Pallas TPU kernels and their jnp oracles (``ref``). Training attention
calls ``flash_attention`` from ``repro.models.attention``, which decides
where the kernel runs (``_fits_kernel``)."""
