"""The benchmark's own modules: nothing here is imported by the program."""
