"""Plain references: independent of the program, float32 at
``precision=highest`` unless a control asks for less."""
