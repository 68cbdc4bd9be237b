import sys

from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

from .driver import main

enable_compile_cache()
sys.exit(main())
