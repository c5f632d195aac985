"""Plain float32 references of architectures, kept beside the program."""
