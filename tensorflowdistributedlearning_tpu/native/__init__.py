"""Native (C++) host-side components, bound via ctypes.

The reference inherited its native IO from TensorFlow's C++ tf.data runtime
(reference: model.py:296-322; SURVEY §2.2). Here the native pieces are first-party:
``io.cc`` provides multithreaded PNG/JPEG decoding with bilinear resize that releases the GIL, compiled on
first use into ``_build/libtfdl_io-{source hash}.so`` and loaded with ctypes (pybind11 is not in
this image). Every native entry point has a pure-Python fallback, so the framework
works even where a C++ toolchain is absent.
"""

from tensorflowdistributedlearning_tpu.native.loader import (
    decode_image_batch,
    decode_png_batch,
    native_available,
)

__all__ = ["decode_image_batch", "decode_png_batch", "native_available"]
