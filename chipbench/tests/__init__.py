"""CPU tests of the benchmark at tiny sizes."""
