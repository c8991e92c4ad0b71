"""The system under test, as far as every model family shares it: the
compile cache, the mesh, a request, the serving counters and the release of
device memory. What builds the program's configuration, trainer and engine
for one family is that family's own ``sut.py``; under benchmark/ only files
of this name import the program. No arithmetic of the yardstick lives here."""
import jax


def use_cache_dir(path):
    """The benchmark's compile cache, at a fixed path inside the checkout.
    The program takes the directory jax's config already holds."""
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import paddle_tpu  # noqa: F401  (sets the program's own jax config)
    return jax.config.jax_compilation_cache_dir


def make_mesh(mesh_axes):
    if not mesh_axes:
        return None
    from paddle_tpu.distributed import env as dist_env
    return dist_env.create_hybrid_mesh(**mesh_axes)


def release_trainer(step):
    step.params = None
    step.opt_state = None
    step._jitted = None


def make_request(prompt, max_new_tokens, on_token):
    """A greedy request with no stop token: ``max_new_tokens`` ends it."""
    from paddle_tpu import serving
    return serving.Request(prompt, max_new_tokens=max_new_tokens,
                           on_token=on_token, do_sample=False)


def serving_counters():
    from paddle_tpu import profiler
    return dict(profiler.serving_counters())


def free_device_memory():
    import gc
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()
