"""Build configuration accessors (reference: python/paddle/sysconfig.py:20,38).

Points at the directories custom-op builds (`utils.custom_op` /
cpp_extension-style workflows) need: the C-ABI sources that define the
native runtime interface, and the lazily-built shared library.
"""
import os

__all__ = ["get_include", "get_lib"]


def get_include():
    """Directory with the framework's native-interface sources
    (sysconfig.py:20). The csrc C-ABI files double as the headers: every
    exported symbol is `extern "C"` with a documented signature."""
    import paddle_tpu

    return os.path.abspath(
        os.path.join(os.path.dirname(paddle_tpu.__file__), os.pardir, "csrc")
    )


def get_lib():
    """Directory containing the runtime library (sysconfig.py:38).

    The runtime builds lazily into <checkout>/.native_build under a name
    keyed by a hash of its sources (runtime/native.py);
    calling this triggers the build so the returned dir actually holds the
    library, matching the reference's contract that get_lib() is linkable.
    """
    from .runtime import native

    if native.lib is None:
        native.build()
    return str(native._CACHE)
