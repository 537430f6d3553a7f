"""Plain float32 references, one per model family, named by the configurations."""
