from .logger import RecursiveLogger
from .profiling import Profiler
