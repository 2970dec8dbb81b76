"""Plain reference implementations the benchmark checks the program against."""
