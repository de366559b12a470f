"""Entry point: ``python -m repro`` → the experiment CLI."""
import sys

from repro.api.cli import main
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()
sys.exit(main())
