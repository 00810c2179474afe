"""The yardstick's library: everything here belongs to the benchmark, not
to the program under test. A module joins by being a file in this
directory: ``registry.load_all()`` imports every one of them, and each
registers its builders, loops, readers and architectures by decorator."""
