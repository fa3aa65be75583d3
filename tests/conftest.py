"""Shared test config: persistent XLA compilation cache.

The tier-1 suite's floor is XLA compile time for the 10 arch smoke tests;
caching compiled executables on disk (content-addressed by jax itself) cuts
repeat runs roughly in half. The cache goes where
``repro.launch.compile_cache`` puts it: ``JAX_COMPILATION_CACHE_DIR`` if
set, else ``<checkout>/artifacts/jax_cache``.
"""
try:
    import jax

    from repro.launch.compile_cache import use_compile_cache
except ImportError:          # the numpy-only CI lanes install no jax
    pass
else:
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
