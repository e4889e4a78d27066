"""JAX's persistent compilation cache for a benchmark process: placed
by the program's own `slate_tpu.utils.compile_cache.enable()` (the
machine's `JAX_COMPILATION_CACHE_DIR` if set, else the fixed
`<checkout>/.jax_cache`), and every program kept however quick its
compile — a solve is hundreds of small programs, and what is not
cached is compiled again in every run's set-up."""


def place():
    from slate_tpu.utils import compile_cache
    directory = compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory
