from tpudist.utils.platform import enable_compilation_cache, tune_tpu

__all__ = ["enable_compilation_cache", "tune_tpu"]
