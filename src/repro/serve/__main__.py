import sys

from repro.runtime.compile_cache import use_compile_cache
from repro.serve.service import main

use_compile_cache()
sys.exit(main())
