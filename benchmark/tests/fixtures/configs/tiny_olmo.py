"""The real configuration's module, on the fixture's toy sizes."""

from benchmark.configs import olmo_hybrid_7b as real

build = real.build
compare = real.compare
